"""Core transformer layers — functional, params as plain dict pytrees.

Conventions
-----------
* ``init_*`` functions return a params dict; ``*_apply`` functions consume it.
* Parameters are stored in ``param_dtype`` (default fp32); compute happens in
  ``dtype`` (default bf16) — weights are cast at use.
* Attention supports GQA, qk-norm, QKV bias, sliding windows, logit softcap,
  dense or KV-chunked (online-softmax) evaluation, and single-token decode
  against a (possibly ring-buffered) KV cache.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig

# --------------------------------------------------------------------------- #
# initializers / basics
# --------------------------------------------------------------------------- #


def _dense_init(key, d_in, d_out, bias=False, scale=None, dtype=jnp.float32):
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x, dtype):
    y = x.astype(dtype) @ p["w"].astype(dtype)
    if "b" in p:
        y = y + p["b"].astype(dtype)
    return y


def init_rmsnorm(d, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * p["scale"].astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #


def rope_cos_sin(positions, head_dim, theta):
    """positions (...,) int32 -> cos/sin of shape (..., head_dim//2), fp32."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x (..., S, H, D); cos/sin (..., S, D//2) broadcast over heads."""
    x = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# --------------------------------------------------------------------------- #
# Attention
# --------------------------------------------------------------------------- #


def init_attention(key, cfg: ModelConfig, dtype=jnp.float32):
    """QKV/O projections stored head-major 3D — (d, H, hd) / (H, hd, d).

    Head-major weights let the partitioner shard the *head* dim explicitly;
    flat (d, H·hd) weights force GSPMD to propagate sharding through a reshape
    whose split does not align with head boundaries when H or Hk is not a
    multiple of the model axis, which degenerates into contraction-dim
    sharding + an all-reduce of the full S×S attention logits (measured: 7.5
    GB/layer/step on qwen2-0.5b before this layout).
    """
    d, h, hk, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 6)

    def proj(k, nheads):
        w = jax.random.normal(k, (d, nheads, hd), dtype) * d ** -0.5
        p = {"w": w}
        if cfg.qkv_bias:
            p["b"] = jnp.zeros((nheads, hd), dtype)
        return p

    p = {
        "wq": proj(ks[0], h),
        "wk": proj(ks[1], hk),
        "wv": proj(ks[2], hk),
        "wo": {"w": jax.random.normal(ks[3], (h, hd, d), dtype)
               * (h * hd) ** -0.5},
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rmsnorm(hd, dtype)
        p["k_norm"] = init_rmsnorm(hd, dtype)
    return p


def _proj_heads(p, x, dtype):
    """x (B,S,d) @ (d,H,hd) -> (B,S,H,hd)."""
    y = jnp.einsum("bsd,dhk->bshk", x.astype(dtype), p["w"].astype(dtype))
    if "b" in p:
        y = y + p["b"].astype(dtype)[None, None]
    return y


def _proj_out(p, x, dtype):
    """x (B,S,H,hd) @ (H,hd,d) -> (B,S,d)."""
    return jnp.einsum("bshk,hkd->bsd", x.astype(dtype), p["w"].astype(dtype))


def _softcap(x, cap):
    return cap * jnp.tanh(x / cap) if cap else x


def _mask_bias(q_pos, k_pos, window, k_valid=None):
    """Additive fp32 mask bias: causal + optional sliding window + validity.

    ``window`` may be: None / 0 (full attention, static), a positive Python int
    (static sliding window), or a traced int32 scalar (per-layer window inside a
    layer scan — gemma3's 5:1 local:global pattern; global layers pass a huge
    value).

    ``q_pos``/``k_pos`` are (Sq,)/(Sk,) for a shared position grid, or carry
    leading batch dims — (B,Sq)/(B,Sk) for per-slot decode positions in the
    continuous-batching ring — giving a (B,Sq,Sk) bias.
    """
    ok = k_pos[..., None, :] <= q_pos[..., :, None]
    if _window_on(window):
        ok &= (q_pos[..., :, None] - k_pos[..., None, :]) < window
    if k_valid is not None:
        ok &= k_valid[..., None, :]
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def _window_on(window) -> bool:
    if window is None:
        return False
    if isinstance(window, int):
        return window > 0
    return True  # traced scalar: always apply (global layers use a huge value)


def _sdpa_dense(q, k, v, q_pos, k_pos, window, softcap, k_valid=None):
    """q (B,Sq,H,D), k/v (B,Sk,Hk,D) -> (B,Sq,H,D).  fp32 softmax.

    Positions are (Sq,)/(Sk,) shared across the batch, or (B,Sq)/(B,Sk) for
    per-slot decode positions (continuous batching).
    """
    B, Sq, H, D = q.shape
    Hk = k.shape[2]
    rep = H // Hk
    qf = q.astype(jnp.float32) * (D ** -0.5)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(B, Sq, Hk, rep, D)
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", qf, kf)
    logits = _softcap(logits, softcap)
    bias = _mask_bias(q_pos, k_pos, window, k_valid)
    # (Sq,Sk) -> (1,1,Sq,Sk) broadcast; (B,Sq,Sk) -> (B,1,1,Sq,Sk)
    logits = logits + bias[..., None, None, :, :]
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhrqk,bkhd->bqhrd", w, vf)
    return out.reshape(B, Sq, H, v.shape[-1])  # v head dim may differ (MLA)


def _sdpa_chunked(q, k, v, q_pos, k_pos, window, softcap, chunk):
    """Online-softmax attention, scanning over KV chunks (bounded memory).

    Differentiable (pure lax.scan); fp32 running (m, l, acc) accumulators.
    """
    B, Sq, H, D = q.shape
    Sk, Hk = k.shape[1], k.shape[2]
    rep = H // Hk
    assert Sk % chunk == 0, (Sk, chunk)
    nC = Sk // chunk
    qf = (q.astype(jnp.float32) * (D ** -0.5)).reshape(B, Sq, Hk, rep, D)
    kc = k.reshape(B, nC, chunk, Hk, D).swapaxes(0, 1)
    vc = v.reshape(B, nC, chunk, Hk, D).swapaxes(0, 1)
    kp = k_pos.reshape(nC, chunk)

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, kpb = xs
        logits = jnp.einsum("bqhrd,bkhd->bhrqk", qf, kb.astype(jnp.float32))
        logits = _softcap(logits, softcap)
        logits = logits + _mask_bias(q_pos, kpb, window)[None, None, None]
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhrqk,bkhd->bhrqd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hk, rep, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, Hk, rep, Sq), jnp.float32)
    a0 = jnp.zeros((B, Hk, rep, Sq, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, a0), (kc, vc, kp))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)


@dataclasses.dataclass
class AttnCall:
    """Runtime knobs for an attention call (not parameters).

    ``window`` may be a Python int (0 = full attention) or a traced int32
    scalar (per-layer windows inside a layer scan). ``force_window`` overrides
    every layer's window (long_500k decode on hybrid/windowed archs).
    """
    window: object = 0
    softcap: float = 0.0
    chunk: int = 0            # 0 = dense; else KV-chunked online softmax
    use_flash_kernel: bool = False  # route through the Pallas kernel (TPU)
    use_decode_kernel: bool = False  # fused single-query decode (kernels/)
    force_window: int = 0
    exact_moe: bool = False   # capacity = N*K (no token drops); tests only
    moe_shard: object = None  # sharding-constraint hook for MoE buffers


@jax.named_scope("attention")
def attention(p, cfg: ModelConfig, x, positions, call: AttnCall, dtype):
    """Full self-attention over x (B,S,d) at integer positions (S,).

    KV is repeated to the full head count before the score einsums so every
    attention tensor is sharded on the (explicit, divisible) head dim — the
    Megatron TP pattern: the only model-axis collective is the psum after the
    output projection. The decode cache still stores the compact Hk heads.
    """
    B, S, _ = x.shape
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _proj_heads(p["wq"], x, dtype)
    k = _proj_heads(p["wk"], x, dtype)
    v = _proj_heads(p["wv"], x, dtype)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin).astype(dtype)
    k = apply_rope(k, cos, sin).astype(dtype)
    cache_kv = (k, v)
    rep = h // hk
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if call.use_flash_kernel and not _window_on(call.window):
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=True, softcap=call.softcap)
    elif call.chunk and S > call.chunk:
        from repro.models.flash import flash_attention_bshd
        win = None if not _window_on(call.window) else call.window
        out = flash_attention_bshd(q, k, v, positions, positions, window=win,
                                   softcap=call.softcap, bq=call.chunk,
                                   bk=call.chunk)
    else:
        out = _sdpa_dense(q, k, v, positions, positions, call.window, call.softcap)
    return _proj_out(p["wo"], out.astype(dtype), dtype), cache_kv


def attention_decode(p, cfg: ModelConfig, x, pos, kcache, vcache, call: AttnCall,
                     dtype):
    """Decode one token: x (B,1,d); cache (B,C,Hk,D).

    ``pos`` is a scalar int32 (one shared position — the classic batched-serve
    path) or a (B,) int32 vector of per-slot positions (continuous batching:
    every slot of the ring is at its own depth in its own sequence).

    The cache may be a ring buffer (C == window) — slot = pos % C; key positions
    are reconstructed so causal/window masking stays correct.
    """
    B = x.shape[0]
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    C = kcache.shape[1]
    q = _proj_heads(p["wq"], x, dtype)
    k = _proj_heads(p["wk"], x, dtype)
    v = _proj_heads(p["wv"], x, dtype)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    idx = jnp.arange(C, dtype=jnp.int32)
    if jnp.ndim(pos) == 0:
        posv = jnp.full((1,), pos, jnp.int32)
        cos, sin = rope_cos_sin(posv, hd, cfg.rope_theta)
        q = apply_rope(q, cos, sin).astype(dtype)
        k = apply_rope(k, cos, sin).astype(dtype)
        slot = jnp.mod(pos, C)
        kcache = jax.lax.dynamic_update_slice(kcache, k.astype(kcache.dtype),
                                              (0, slot, 0, 0))
        vcache = jax.lax.dynamic_update_slice(vcache, v.astype(vcache.dtype),
                                              (0, slot, 0, 0))
        # reconstruct absolute positions of cache slots for a ring buffer
        wrap = (pos // C) * C
        k_pos = jnp.where(idx <= slot, wrap + idx, wrap - C + idx)
        q_pos = posv
    else:
        posb = pos.astype(jnp.int32)                     # (B,)
        cos, sin = rope_cos_sin(posb[:, None], hd, cfg.rope_theta)  # (B,1,·)
        q = apply_rope(q, cos, sin).astype(dtype)
        k = apply_rope(k, cos, sin).astype(dtype)
        slot = jnp.mod(posb, C)                          # (B,)
        barange = jnp.arange(B)
        kcache = kcache.at[barange, slot].set(k[:, 0].astype(kcache.dtype))
        vcache = vcache.at[barange, slot].set(v[:, 0].astype(vcache.dtype))
        wrap = (posb // C) * C                           # (B,)
        k_pos = jnp.where(idx[None, :] <= slot[:, None],
                          wrap[:, None] + idx[None, :],
                          wrap[:, None] - C + idx[None, :])  # (B,C)
        q_pos = posb[:, None]                            # (B,1)
    k_valid = k_pos >= 0
    if call.use_decode_kernel:
        from repro.kernels import ops as kops
        bias = _mask_bias(q_pos, k_pos, call.window, k_valid)  # (·,1?,C)
        bias = jnp.broadcast_to(bias.reshape(-1, C), (B, C))
        out = kops.decode_attention(q[:, 0], kcache, vcache, bias,
                                    softcap=call.softcap)[:, None]
    else:
        out = _sdpa_dense(q, kcache, vcache, q_pos, k_pos, call.window,
                          call.softcap, k_valid=k_valid)
    return _proj_out(p["wo"], out.astype(dtype), dtype), kcache, vcache


# --------------------------------------------------------------------------- #
# Gated MLP (SwiGLU / GeGLU)
# --------------------------------------------------------------------------- #


def init_mlp(key, d, f, dtype=jnp.float32):
    ks = jax.random.split(key, 3)
    return {
        "wg": _dense_init(ks[0], d, f, dtype=dtype),
        "wu": _dense_init(ks[1], d, f, dtype=dtype),
        "wd": _dense_init(ks[2], f, d, dtype=dtype),
    }


def mlp(p, x, act, dtype):
    g = linear(p["wg"], x, dtype)
    u = linear(p["wu"], x, dtype)
    a = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    return linear(p["wd"], a * u, dtype)


# --------------------------------------------------------------------------- #
# Embedding / head
# --------------------------------------------------------------------------- #


def padded_vocab(v, multiple=2048):
    return ((v + multiple - 1) // multiple) * multiple


def init_embed(key, cfg: ModelConfig, dtype=jnp.float32):
    V = padded_vocab(cfg.vocab_size)
    p = {"table": jax.random.normal(key, (V, cfg.d_model), dtype) * 0.02}
    if not cfg.tie_embeddings:
        k2 = jax.random.fold_in(key, 1)
        p["head"] = jax.random.normal(k2, (cfg.d_model, V), dtype) \
            * cfg.d_model ** -0.5
    return p


def embed(p, tokens, dtype):
    return p["table"].astype(dtype)[tokens]


@jax.named_scope("lm_head")
def unembed(p, x, cfg: ModelConfig, dtype):
    if cfg.tie_embeddings:
        logits = x.astype(dtype) @ p["table"].astype(dtype).T
        logits = logits * (cfg.d_model ** -0.5)  # gemma-style tied-head scaling
    else:
        logits = x.astype(dtype) @ p["head"].astype(dtype)
    return logits


@jax.named_scope("lm_head")
def cross_entropy(logits, labels, vocab_size):
    """Mean CE over positions; labels < 0 are masked out; padded vocab masked."""
    V = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if V > vocab_size:
        pad_mask = jnp.arange(V) >= vocab_size
        logits = jnp.where(pad_mask, -1e30, logits)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = logz - gold
    mask = (labels >= 0).astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


def head_path(cfg: ModelConfig, tokens: int, *, devices: int = 1,
              backend: Optional[str] = None) -> str:
    """How ``unembed_cross_entropy`` computes the loss of ``tokens`` tokens:
    "fused", one Pallas kernel pair that never writes the (T, V) logits
    (``kernels/fused_ce.py``), when the backend is a TPU, the step is built
    for one device (a ``pallas_call`` over GSPMD-sharded activations would
    be gathered onto every device, as ``engine._one_pass`` says of the
    sync), the head has no softcap, matmuls take XLA's default precision
    and the shapes tile (``ops.fused_ce_tiles``); else "jnp", ``unembed``
    then ``cross_entropy``."""
    from repro.kernels import ops as kops
    fused = ((backend or jax.default_backend()) == "tpu" and devices == 1
             and not cfg.logit_softcap
             and jax.config.jax_default_matmul_precision in (
                 None, "default", "bfloat16")
             and kops.fused_ce_tiles(tokens, padded_vocab(cfg.vocab_size),
                                     cfg.d_model) is not None)
    return "fused" if fused else "jnp"


def unembed_cross_entropy(p, x, labels, cfg: ModelConfig, dtype,
                          devices: int = 1):
    """``cross_entropy(unembed(p, x, ...), labels, ...)``, by the path that
    ``head_path`` picks for a step built for ``devices`` devices."""
    if head_path(cfg, labels.size, devices=devices) == "jnp":
        return cross_entropy(unembed(p, x, cfg, dtype), labels,
                             cfg.vocab_size)
    from repro.kernels import ops as kops
    with jax.named_scope("lm_head"), jax.named_scope("fused_ce"):
        w = p["table"] if cfg.tie_embeddings else p["head"]
        return kops.fused_cross_entropy(
            x.astype(dtype).reshape(labels.size, -1), w.astype(dtype),
            labels.reshape(-1), tied=cfg.tie_embeddings,
            scale=cfg.d_model ** -0.5, v_real=cfg.vocab_size)
