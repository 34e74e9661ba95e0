"""Fused LM head and cross-entropy — Pallas TPU kernels.

The training loss's tail, ``models/layers.py:unembed`` then
``cross_entropy``: logits = scale·h·Wᵀ against a tied ``(V, d)`` table, or
h·W against an untied ``(d, V)`` head; the padded vocab rows (index ≥
``v_real``) masked to −1e30; the mean over the tokens with a label ≥ 0 of
lse(logits) − logits[label]. XLA writes the ``(T, V)`` fp32 logits to HBM
and passes over them several times, forward and backward. Here they exist
only as ``(tm, tv)`` tiles in VMEM:

* forward — grid (token tile i, vocab tile j), j innermost. Each step folds
  one logits tile into a running max and sum and picks out the gold logits
  of its tokens; the last vocab tile writes ``lse`` and ``gold``. The
  backward keeps h, W and ``lse`` (T·4 bytes) besides the labels.
* backward — grid (vocab tile j, token tile i), i innermost. Each step
  recomputes its logits tile and forms dl = c·(softmax − onehot), with
  c = g·mask·scale/n for each token, then adds dl·W to dh, which stays in
  VMEM through the whole grid, and dlᵀ·h to W's gradient tile, which stays
  while i runs. That is one head matmul more than XLA's three, and no
  logits-sized buffer.

The MXU's operands are ``mxu_dtype`` with fp32 accumulation; the softmax
and every sum are fp32. ``ops.fused_cross_entropy`` gives the operands the
precision that XLA's default gives an fp32 matmul on the backend: one
bfloat16 pass on a TPU. ``vmap`` over clients batches both kernels (a
leading grid axis).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30                      # a padded vocab row's logit, as cross_entropy
VMEM_LIMIT = 100 << 20           # of v5e's 128 MiB VMEM
VMEM_BUDGET = 64 << 20           # what the blocks may take; temporaries beside
TOKEN_TILES = (1024, 512, 256, 128)
VOCAB_TILES = (2048, 1024, 512, 256)
LANE = 128                       # a (t, 1) column takes (t, 128) in VMEM


def _fwd_bytes(tm, tv, d):
    """VMEM of the forward's blocks (inputs and outputs double-buffered),
    its scratch and its (tm, tv) fp32 temporaries."""
    return (2 * tm * d * 2 + 2 * tv * d * 4 + tv * d * 2
            + 7 * tm * LANE * 4 + 4 * tm * tv * 4)


def _bwd_bytes(T, tm, tv, d):
    """VMEM of the backward: its blocks, the bf16 copy of W's tile, the
    resident (T, d) dh (one buffer: it is written back once a client) and
    the (tm, tv) temporaries."""
    return (2 * tm * d * 2 + 4 * tv * d * 4 + tv * d * 2 + T * d * 4
            + 6 * tm * LANE * 4 + 4 * tm * tv * 4)


class Tiles(NamedTuple):
    fwd: tuple                   # (tm, tv)
    bwd: tuple                   # (tm, tv)


def tiles(T: int, V: int, d: int) -> Optional[Tiles]:
    """The token and vocab tiles of each kernel for T tokens against V
    vocab rows of width d, or None where the shapes do not tile: T a
    multiple of the smallest token tile, V of the smallest vocab tile, and
    the backward's resident dh within the VMEM budget. The forward takes
    the largest token tile (each one reads all of W), the backward the
    largest vocab tile (each one reads all of h)."""
    tms = [t for t in TOKEN_TILES if T % t == 0]
    tvs = [t for t in VOCAB_TILES if V % t == 0]
    fwd = next(((tm, tv) for tm in tms for tv in tvs
                if _fwd_bytes(tm, tv, d) <= VMEM_BUDGET), None)
    bwd = next(((tm, tv) for tv in tvs for tm in tms if tm <= 512
                and _bwd_bytes(T, tm, tv, d) <= VMEM_BUDGET), None)
    return Tiles(fwd, bwd) if fwd and bwd else None


class _Static(NamedTuple):
    tied: bool
    scale: float
    v_real: int
    mxu: str                     # the MXU operands' dtype name
    tiles: Tiles
    h_dtype: str
    interpret: bool


def _logits(h, w, j, tv, st):
    """One (tm, tv) fp32 logits tile of vocab tile j, padded rows masked,
    and its vocab indices."""
    if st.tied:     # h (tm, d) · w (tv, d)ᵀ
        s = jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * st.scale
    else:           # h (tm, d) · w (d, tv)
        s = jnp.dot(h, w, preferred_element_type=jnp.float32)
    col = j * tv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(col < st.v_real, s, NEG), col


def _fwd_kernel(h_ref, w_ref, y_ref, lse_ref, gold_ref, m_sc, l_sc, g_sc,
                *, tv, nv, st):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        g_sc[...] = jnp.zeros(g_sc.shape, jnp.float32)

    s, col = _logits(h_ref[...], w_ref[...].astype(st.mxu), j, tv, st)
    m_old = m_sc[...]
    m_new = jnp.maximum(m_old, s.max(axis=1, keepdims=True))
    l_sc[...] = l_sc[...] * jnp.exp(m_old - m_new) + jnp.exp(
        s - m_new).sum(axis=1, keepdims=True)
    m_sc[...] = m_new
    g_sc[...] += jnp.where(col == y_ref[...], s, 0.0).sum(axis=1,
                                                          keepdims=True)

    @pl.when(j == nv - 1)
    def _():
        lse_ref[...] = m_sc[...] + jnp.log(l_sc[...])
        gold_ref[...] = g_sc[...]


def _bwd_kernel(h_ref, w_ref, y_ref, lse_ref, c_ref, dh_ref, dw_ref, wb_sc,
                *, tm, tv, st):
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (i == 0))
    def _():
        dh_ref[...] = jnp.zeros(dh_ref.shape, jnp.float32)

    @pl.when(i == 0)
    def _():
        wb_sc[...] = w_ref[...].astype(st.mxu)
        dw_ref[...] = jnp.zeros(dw_ref.shape, jnp.float32)

    h, w = h_ref[...], wb_sc[...]
    s, col = _logits(h, w, j, tv, st)
    p = jnp.exp(s - lse_ref[...])
    dl = (jnp.where(col == y_ref[...], p - 1.0, p) * c_ref[...]
          ).astype(st.mxu)
    rows = pl.ds(pl.multiple_of(i * tm, tm), tm)
    f32 = jnp.float32
    if st.tied:     # dh += dl·w, dW += dlᵀ·h
        dh_ref[rows, :] += jnp.dot(dl, w, preferred_element_type=f32)
        dw_ref[...] += jax.lax.dot_general(
            dl, h, (((0,), (0,)), ((), ())), preferred_element_type=f32)
    else:           # dh += dl·wᵀ, dW += hᵀ·dl
        dh_ref[rows, :] += jax.lax.dot_general(
            dl, w, (((1,), (1,)), ((), ())), preferred_element_type=f32)
        dw_ref[...] += jax.lax.dot_general(
            h, dl, (((0,), (0,)), ((), ())), preferred_element_type=f32)


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_LIMIT)


def _w_spec(st, tv, d, index):
    """W's vocab tile ``index(...)`` of its (V, d) or (d, V) layout."""
    if st.tied:
        return pl.BlockSpec((tv, d), lambda *g: (index(*g), 0))
    return pl.BlockSpec((d, tv), lambda *g: (0, index(*g)))


def _forward(h, w, y, st):
    """(lse, gold), each (T, 1) fp32."""
    T, d = h.shape
    V = w.shape[0] if st.tied else w.shape[1]
    tm, tv = st.tiles.fwd
    nt, nv = T // tm, V // tv
    col = pl.BlockSpec((tm, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tv=tv, nv=nv, st=st),
        grid=(nt, nv),
        in_specs=[pl.BlockSpec((tm, d), lambda i, j: (i, 0)),
                  _w_spec(st, tv, d, lambda i, j: j), col],
        out_specs=[col, col],
        out_shape=[jax.ShapeDtypeStruct((T, 1), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((tm, 1), jnp.float32)] * 3,
        compiler_params=_params("parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * T * V * d, transcendentals=T * V,
            bytes_accessed=h.size * h.dtype.itemsize
            + nt * w.size * w.dtype.itemsize + 12 * T),
        interpret=st.interpret,
    )(h, w, y)


def _backward(h, w, y, lse, c, st):
    """(dh (T, d), dW in W's layout), fp32."""
    T, d = h.shape
    V = w.shape[0] if st.tied else w.shape[1]
    tm, tv = st.tiles.bwd
    nt, nv = T // tm, V // tv
    col = pl.BlockSpec((tm, 1), lambda j, i: (i, 0))
    w_spec = _w_spec(st, tv, d, lambda j, i: j)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tm=tm, tv=tv, st=st),
        grid=(nv, nt),
        in_specs=[pl.BlockSpec((tm, d), lambda j, i: (i, 0)), w_spec,
                  col, col, col],
        out_specs=[pl.BlockSpec((T, d), lambda j, i: (0, 0),
                                pipeline_mode=pl.Buffered(1)), w_spec],
        out_shape=[jax.ShapeDtypeStruct((T, d), jnp.float32),
                   jax.ShapeDtypeStruct(w.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((tv, d) if st.tied else (d, tv),
                                   st.mxu)],
        compiler_params=_params("arbitrary", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=6 * T * V * d, transcendentals=T * V,
            bytes_accessed=nv * h.size * h.dtype.itemsize
            + w.size * (w.dtype.itemsize + 4) + 4 * T * d + 12 * T),
        interpret=st.interpret,
    )(h, w, y, lse, c)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _loss(h, w, y, st):
    return _loss_fwd(h, w, y, st)[0]


def _loss_fwd(h, w, y, st):
    hm = h.astype(st.mxu)
    lse, gold = _forward(hm, w, y, st)
    mask = (y >= 0).astype(jnp.float32)
    n = jnp.maximum(mask.sum(), 1.0)
    return ((lse - gold) * mask).sum() / n, (hm, w, y, lse)


def _loss_bwd(st, res, g):
    hm, w, y, lse = res
    mask = (y >= 0).astype(jnp.float32)
    n = jnp.maximum(mask.sum(), 1.0)
    c = mask * (g / n * (st.scale if st.tied else 1.0))
    dh, dw = _backward(hm, w, y, lse, c, st)
    return dh.astype(st.h_dtype), dw.astype(w.dtype), None


_loss.defvjp(_loss_fwd, _loss_bwd)


def fused_cross_entropy(h, w, labels, *, tied: bool, scale: float,
                        v_real: int, mxu_dtype=jnp.bfloat16,
                        tile: Optional[Tiles] = None, interpret=False):
    """Mean cross-entropy of the head's logits, as ``cross_entropy(unembed(
    ...))`` computes it, without the logits in HBM.

    ``h`` (T, d) the final hidden states; ``w`` the tied (V, d) table, its
    logits scaled by ``scale``, or the untied (d, V) head; ``labels`` (T,)
    int32, those < 0 masked out; vocab rows ≥ ``v_real`` masked out of the
    softmax. ``tile`` defaults to ``tiles(T, V, d)``. Differentiable in h
    and w (``jax.custom_vjp``); fp32 scalar out.
    """
    T, d = h.shape
    V = w.shape[0] if tied else w.shape[1]
    tile = tile or tiles(T, V, d)
    if tile is None:
        raise ValueError(f"fused_cross_entropy: {T} tokens against {V} "
                         f"vocab rows of width {d} do not tile")
    st = _Static(tied=bool(tied), scale=float(scale), v_real=int(v_real),
                 mxu=jnp.dtype(mxu_dtype).name, tiles=Tiles(*tile),
                 h_dtype=jnp.dtype(h.dtype).name, interpret=bool(interpret))
    return _loss(h, w, labels.reshape(T, 1).astype(jnp.int32), st)
