"""Mamba2 — State Space Duality (SSD) blocks (arXiv:2405.21060).

Training/prefill uses the chunked SSD algorithm: intra-chunk quadratic
(attention-like, MXU-friendly) term + inter-chunk linear state recurrence via
``lax.scan`` over chunks. Decode carries a constant-size recurrent state
(B, H, P, N) plus depthwise-conv tails — O(1) per token regardless of context
length, which is why the ssm/hybrid archs run ``long_500k``.

TPU adaptation notes (vs. the CUDA kernels of the paper): chunked einsums are
shaped (chunk × head_dim/state) so the MXU sees >=128-sized contractions; the
inter-chunk recurrence stays a scan (sequential over S/chunk steps, trivially
cheap). A Pallas kernel for the fused intra-chunk term lives in
kernels/ssd_scan.py; this module is the pure-JAX reference path used by
default (identical math).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models.layers import _dense_init, init_rmsnorm, linear, rmsnorm


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    nheads = d_in // s.head_dim
    return s, d_in, nheads


def init_mamba2(key, cfg: ModelConfig, dtype=jnp.float32):
    s, d_in, nh = _dims(cfg)
    gn = s.ngroups * s.d_state
    d = cfg.d_model
    ks = jax.random.split(key, 10)
    return {
        "wx": _dense_init(ks[0], d, d_in, dtype=dtype),
        "wz": _dense_init(ks[1], d, d_in, dtype=dtype),
        "wB": _dense_init(ks[2], d, gn, dtype=dtype),
        "wC": _dense_init(ks[3], d, gn, dtype=dtype),
        "wdt": _dense_init(ks[4], d, nh, dtype=dtype),
        "conv_x": jax.random.normal(ks[5], (d_in, s.d_conv), dtype) * 0.1,
        "conv_B": jax.random.normal(ks[6], (gn, s.d_conv), dtype) * 0.1,
        "conv_C": jax.random.normal(ks[7], (gn, s.d_conv), dtype) * 0.1,
        "dt_bias": jnp.zeros((nh,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, nh, dtype=dtype)),
        "Dskip": jnp.ones((nh,), dtype),
        "gate_norm": init_rmsnorm(d_in, dtype),
        "wo": _dense_init(ks[8], d_in, d, dtype=dtype),
    }


def _causal_conv(x, w):
    """Depthwise causal conv: x (B,S,C), w (C,K) -> (B,S,C)."""
    K = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    # sum_k x[t-K+1+k] * w[:,k]
    out = jnp.zeros_like(x)
    for k in range(K):
        out = out + xp[:, k: k + x.shape[1], :] * w[None, None, :, k]
    return out


def _segsum_exp(cum):
    """cum (..., Q) cumulative dA -> L (..., Q, Q); L[i,j]=exp(cum_i-cum_j), i>=j.

    Mask BEFORE exp: upper-triangle diffs are positive and can overflow to
    inf, which poisons the backward of where (0·inf = NaN in the exp VJP).
    """
    Q = cum.shape[-1]
    diff = cum[..., :, None] - cum[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.exp(jnp.where(mask, diff, -1e30)) * mask


@jax.named_scope("ssd_scan")
def ssd_chunked(xh, dt, A, Bm, Cm, chunk, h0=None):
    """Chunked SSD scan.

    xh (B,S,H,P) input heads; dt (B,S,H) >0; A (H,) <0;
    Bm/Cm (B,S,H,N) per-head (groups pre-broadcast). Returns (y (B,S,H,P),
    h_final (B,H,P,N)).
    """
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    Q = chunk
    assert S % Q == 0, (S, Q)
    nc = S // Q

    f32 = jnp.float32
    dA = (dt.astype(f32) * A.astype(f32)[None, None, :])          # (B,S,H)
    xdt = xh.astype(f32) * dt.astype(f32)[..., None]              # (B,S,H,P)

    def r(t, last=None):
        return t.reshape((Bsz, nc, Q) + t.shape[2:])

    dA_c, xdt_c = r(dA), r(xdt)
    B_c, C_c = r(Bm.astype(f32)), r(Cm.astype(f32))
    cum = jnp.cumsum(dA_c, axis=2)                                # (B,nc,Q,H)

    # intra-chunk (quadratic, MXU): Y[i] = sum_{j<=i} C_i·B_j L_ij x_j dt_j
    L = _segsum_exp(cum.transpose(0, 1, 3, 2))                    # (B,nc,H,Q,Q)
    G = jnp.einsum("bcihn,bcjhn->bchij", C_c, B_c)                # (B,nc,H,Q,Q)
    Y_diag = jnp.einsum("bchij,bchij,bcjhp->bcihp", G, L, xdt_c)

    # end-of-chunk states
    decay_out = jnp.exp(cum[:, :, -1:, :] - cum)                  # (B,nc,Q,H)
    S_c = jnp.einsum("bcjhn,bcjh,bcjhp->bchpn", B_c, decay_out, xdt_c)
    total = jnp.exp(cum[:, :, -1, :])                             # (B,nc,H)

    def step(h, xs):
        s_c, tot = xs
        h_next = tot[..., None, None] * h + s_c
        return h_next, h                                          # emit pre-update

    h0 = jnp.zeros((Bsz, H, P, N), f32) if h0 is None else h0.astype(f32)
    h_fin, h_prevs = jax.lax.scan(step, h0, (S_c.swapaxes(0, 1),
                                             total.swapaxes(0, 1)))
    h_prevs = h_prevs.swapaxes(0, 1)                              # (B,nc,H,P,N)

    decay_in = jnp.exp(cum)                                       # (B,nc,Q,H)
    Y_off = jnp.einsum("bcihn,bcih,bchpn->bcihp", C_c, decay_in, h_prevs)
    y = (Y_diag + Y_off).reshape(Bsz, S, H, P)
    return y, h_fin


def _conv_tail(x, K):
    """Last K-1 causal-conv inputs (left zero-padded when S < K-1): the conv
    state a decode step starting at pos = S expects."""
    S = x.shape[1]
    if S >= K - 1:
        return x[:, S - (K - 1):, :]
    return jnp.pad(x, ((0, 0), (K - 1 - S, 0), (0, 0)))


def mamba2_forward(p, cfg: ModelConfig, u, dtype, h0=None, return_state=False,
                   return_cache=False):
    """u (B,S,d) -> (B,S,d). Full-sequence (train / prefill).

    ``return_cache=True`` additionally returns a decode cache (same pytree as
    ``mamba2_init_cache``) positioned after the last token: the final SSD
    state plus the depthwise-conv input tails — what serving needs to continue
    decoding at pos = S without replaying the prompt.
    """
    s, d_in, nh = _dims(cfg)
    Bsz, S, _ = u.shape
    x_pre = linear(p["wx"], u, dtype)
    B_pre = linear(p["wB"], u, dtype)
    C_pre = linear(p["wC"], u, dtype)
    x = _causal_conv(x_pre, p["conv_x"].astype(dtype))
    Bm = _causal_conv(B_pre, p["conv_B"].astype(dtype))
    Cm = _causal_conv(C_pre, p["conv_C"].astype(dtype))
    x, Bm, Cm = jax.nn.silu(x), jax.nn.silu(Bm), jax.nn.silu(Cm)
    z = linear(p["wz"], u, dtype)
    dt = jax.nn.softplus(linear(p["wdt"], u, jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    xh = x.reshape(Bsz, S, nh, s.head_dim)
    rep = nh // s.ngroups
    Bh = jnp.repeat(Bm.reshape(Bsz, S, s.ngroups, s.d_state), rep, axis=2)
    Ch = jnp.repeat(Cm.reshape(Bsz, S, s.ngroups, s.d_state), rep, axis=2)

    y, h_fin = ssd_chunked(xh, dt, A, Bh, Ch, min(s.chunk, S), h0=h0)
    y = y + p["Dskip"].astype(jnp.float32)[None, None, :, None] \
        * xh.astype(jnp.float32)
    y = y.reshape(Bsz, S, d_in).astype(dtype)
    y = rmsnorm(p["gate_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = linear(p["wo"], y, dtype)
    if return_cache:
        K = s.d_conv
        cache = {"h": h_fin, "conv_x": _conv_tail(x_pre, K),
                 "conv_B": _conv_tail(B_pre, K), "conv_C": _conv_tail(C_pre, K)}
        return out, cache
    if return_state:
        return out, h_fin
    return out


def mamba2_init_cache(cfg: ModelConfig, batch, dtype=jnp.float32):
    s, d_in, nh = _dims(cfg)
    gn = s.ngroups * s.d_state
    # conv tails stay fp32 like h: _conv_step promotes the rolled window to
    # fp32 anyway, and the cache dtype must be a fixed point of the decode
    # step (the continuous-batching slot insert requires leaf dtypes to
    # round-trip). K-1 rows per layer — negligible memory.
    return {
        "h": jnp.zeros((batch, nh, s.head_dim, s.d_state), jnp.float32),
        "conv_x": jnp.zeros((batch, s.d_conv - 1, d_in), jnp.float32),
        "conv_B": jnp.zeros((batch, s.d_conv - 1, gn), jnp.float32),
        "conv_C": jnp.zeros((batch, s.d_conv - 1, gn), jnp.float32),
    }


def _conv_step(state, xt, w):
    """state (B,K-1,C), xt (B,C), w (C,K) -> (out (B,C), new_state)."""
    window = jnp.concatenate([state, xt[:, None, :]], axis=1)     # (B,K,C)
    out = jnp.einsum("bkc,ck->bc", window, w)
    return out, window[:, 1:, :]


def mamba2_decode(p, cfg: ModelConfig, u, cache, dtype):
    """u (B,1,d) -> (B,1,d); O(1) state update."""
    s, d_in, nh = _dims(cfg)
    Bsz = u.shape[0]
    ut = u[:, 0, :]
    x_t = linear(p["wx"], ut, dtype)
    B_t = linear(p["wB"], ut, dtype)
    C_t = linear(p["wC"], ut, dtype)
    x_t, cx = _conv_step(cache["conv_x"], x_t, p["conv_x"].astype(dtype))
    B_t, cb = _conv_step(cache["conv_B"], B_t, p["conv_B"].astype(dtype))
    C_t, cc = _conv_step(cache["conv_C"], C_t, p["conv_C"].astype(dtype))
    x_t, B_t, C_t = jax.nn.silu(x_t), jax.nn.silu(B_t), jax.nn.silu(C_t)
    z = linear(p["wz"], ut, dtype)
    dt = jax.nn.softplus(linear(p["wdt"], ut, jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))      # (B,H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    xh = x_t.reshape(Bsz, nh, s.head_dim).astype(jnp.float32)
    rep = nh // s.ngroups
    Bh = jnp.repeat(B_t.reshape(Bsz, s.ngroups, s.d_state), rep, 1).astype(jnp.float32)
    Ch = jnp.repeat(C_t.reshape(Bsz, s.ngroups, s.d_state), rep, 1).astype(jnp.float32)

    dA = jnp.exp(dt * A[None, :])                                 # (B,H)
    h = cache["h"] * dA[..., None, None] \
        + jnp.einsum("bh,bhp,bhn->bhpn", dt, xh, Bh)
    y = jnp.einsum("bhpn,bhn->bhp", h, Ch) \
        + p["Dskip"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(Bsz, d_in).astype(dtype)
    y = rmsnorm(p["gate_norm"], y * jax.nn.silu(z), cfg.norm_eps)
    out = linear(p["wo"], y, dtype)[:, None, :]
    new_cache = {"h": h, "conv_x": cx, "conv_B": cb, "conv_C": cc}
    return out, new_cache


def ssd_reference(xh, dt, A, Bm, Cm):
    """Naive sequential SSD (oracle for tests): O(S) python-free scan."""
    Bsz, S, H, P = xh.shape
    N = Bm.shape[-1]
    f32 = jnp.float32

    def step(h, xs):
        x_t, dt_t, B_t, C_t = xs
        dA = jnp.exp(dt_t.astype(f32) * A.astype(f32)[None, :])   # (B,H)
        h = h * dA[..., None, None] + jnp.einsum(
            "bh,bhp,bhn->bhpn", dt_t.astype(f32), x_t.astype(f32),
            B_t.astype(f32))
        y = jnp.einsum("bhpn,bhn->bhp", h, C_t.astype(f32))
        return h, y

    h0 = jnp.zeros((Bsz, H, P, N), f32)
    xs = (xh.swapaxes(0, 1), dt.swapaxes(0, 1), Bm.swapaxes(0, 1),
          Cm.swapaxes(0, 1))
    h_fin, ys = jax.lax.scan(step, h0, xs)
    return ys.swapaxes(0, 1), h_fin
