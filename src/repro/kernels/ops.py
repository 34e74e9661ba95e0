"""Jit'd public wrappers around the Pallas kernels.

On the CPU backend every kernel runs in interpret mode — the kernel body
executes in Python on CPU, which is the validation path; on TPU the same calls
compile to Mosaic. Any other backend is an error (``_interpret``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import decode_step as _ds
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_ce as _fce
from repro.kernels import quantize_update as _qu
from repro.kernels import scaled_update as _su
from repro.kernels import ssd_scan as _ssd
from repro.kernels import sync_average as _sa
from repro.utils.tree import tree_from_paths


def _interpret() -> bool:
    """Interpret mode on the CPU backend, Mosaic on the TPU, an error
    anywhere else: a kernel never runs interpreted on an accelerator."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels run on cpu (interpreted) or tpu, "
                           f"not on backend {backend!r}")
    return backend == "cpu"


def scaled_update(p, m, g, d, *, gamma, beta1, alpha, squared=True):
    """Fused SAVIC step on arbitrarily-shaped arrays."""
    shape = p.shape
    flat = lambda x: x.reshape(-1).astype(jnp.float32)
    po, mo = _su.scaled_update_flat(flat(p), flat(m), flat(g), flat(d),
                                    gamma=float(gamma), beta1=float(beta1),
                                    alpha=float(alpha), squared=squared,
                                    interpret=_interpret())
    return po.reshape(shape).astype(p.dtype), mo.reshape(shape).astype(m.dtype)


def scaled_update_tree(params, mom, d_tree, gamma, alpha, squared=True):
    """Tree version used by core/savic.py (beta1 pre-applied in mom)."""
    out_p, out_m = {}, {}
    flat_p = jax.tree.leaves(params)
    flat_m = jax.tree.leaves(mom)
    flat_d = jax.tree.leaves(d_tree)
    treedef = jax.tree.structure(params)
    news = [scaled_update(p, jnp.zeros_like(m), m, d, gamma=gamma, beta1=0.0,
                          alpha=alpha, squared=squared)[0]
            for p, m, d in zip(flat_p, flat_m, flat_d)]
    return jax.tree.unflatten(treedef, news)


def fused_local_step(p, m, g, d=None, h=None, t=None, s=None, *, gamma, beta1,
                     weight_decay=0.0, alpha, beta2=0.999, kind, clip="max",
                     schedule="const", update_d=False):
    """One fused generic-scaling local step on (M, n) flat client buffers.

    Fuses the D̂ update (rule-2/rule-3/AdaGrad, const or debias β_t) with
    the momentum and scaled parameter update in ONE ``pallas_call`` covering
    all M rows. No engine path calls it yet (DESIGN.md §7).
    ``d`` is (M, n) for local scaling, (n,) for global, None for identity;
    ``h`` is the external (Hutchinson) stat; ``t``/``s`` are per-client step
    counters / grad-clip scales (scalar prefetch). Returns (p', m', d'|None).
    """
    return _su.fused_step_flat(p, m, g, d, h, t, s, gamma=float(gamma),
                               beta1=float(beta1),
                               weight_decay=float(weight_decay),
                               alpha=float(alpha), beta2=float(beta2),
                               kind=kind, clip=clip, schedule=schedule,
                               update_d=update_d, interpret=_interpret())


def quantize_update(x, u, scale):
    """Fused stochastic int8 encode + fp32 decode on arbitrarily-shaped arrays.

    ``u`` are U[0,1) draws shaped like x; ``scale`` broadcasts to x.shape
    (per-client absmax/127 in the engine). Returns (q int8, decoded fp32)
    with x's shape.
    """
    shape = x.shape
    flat = lambda a: jnp.broadcast_to(a, shape).reshape(-1).astype(jnp.float32)
    q, dec = _qu.quantize_update_flat(flat(x), flat(u), flat(scale),
                                      interpret=_interpret())
    return q.reshape(shape), dec.reshape(shape).astype(x.dtype)


sync_tiles = _sa.tiles


def sync_average(x, w, *, drift=False, layer_major=False):
    """One-pass sync of an ``(M, …)`` fp32 client leaf that ``sync_tiles``:
    every client slot gets Σ_m w_m·x_m, written over x's buffer, or read
    layer-major from an ``(M, L, …)`` stack (``sync_average.py``). Returns
    ``(out, drift_sum)``, ``drift_sum`` = Σ_m ‖x_m − x̄‖² (x̄ the unweighted
    mean) when ``drift``, else None."""
    return _sa.sync_average(x, w, drift=drift, layer_major=layer_major,
                            interpret=_interpret())


fused_ce_tiles = _fce.tiles


def fused_cross_entropy(h, w, labels, *, tied, scale, v_real):
    """The LM head and its mean cross-entropy in one kernel pair that never
    writes the (T, V) logits (``fused_ce.py``): h (T, d), w the tied (V, d)
    table or the untied (d, V) head, labels (T,). The matmuls take the
    precision XLA's default gives an fp32 matmul on the backend: one
    bfloat16 pass on a TPU, fp32 on the CPU."""
    interpret = _interpret()
    return _fce.fused_cross_entropy(
        h, w, labels, tied=tied, scale=scale, v_real=v_real,
        mxu_dtype=jnp.float32 if interpret else jnp.bfloat16,
        interpret=interpret)


def flash_attention(q, k, v, *, causal=True, window=0, softcap=0.0,
                    bq=128, bk=128):
    """(B,S,H,D) layout in, (B,S,H,D) out (transposes to kernel layout)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    ot = _fa.flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                                  softcap=softcap, bq=bq, bk=bk,
                                  interpret=_interpret())
    return ot.transpose(0, 2, 1, 3)


def decode_attention(q, k, v, bias, *, softcap=0.0):
    """Fused single-query decode attention against one KV ring.

    q (B,H,D); k/v (B,C,Hk,D/Dv) in decode-cache layout; bias (B,C) additive
    fp32 mask (causal + window + ring validity, precomputed by the caller).
    Returns (B,H,Dv) fp32 — bitwise-equal to ``ref.decode_attention_ref``.
    """
    return _ds.decode_attention(q, k, v, bias, softcap=float(softcap),
                                interpret=_interpret())


def decode_sample(y, table, noise, *, scale, v_real, block=None):
    """Fused unembed + gumbel-argmax sampling tail.

    y (B,d) final hidden; table (V,d); noise (B,V) fp32 (zeros = greedy).
    Returns token ids (B,) int32 without materialising the (B,V) logits —
    bitwise-equal to ``ref.decode_sample_ref``. ``block=None`` sizes the
    vocab block to VMEM (``decode_step.vocab_block``).
    """
    return _ds.decode_sample(y, table, noise, scale=float(scale),
                             v_real=int(v_real), block=block,
                             interpret=_interpret())


def ssd(xh, dt, A, Bm, Cm, *, chunk):
    """Chunked SSD via the Pallas intra-chunk kernel + host inter-chunk scan."""
    return _ssd.ssd_kernel_forward(xh, dt, A, Bm, Cm, chunk,
                                   interpret=_interpret())
