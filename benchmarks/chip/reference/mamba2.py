"""Plain reference of the Mamba-2 language model (arXiv:2405.21060) and
its next-token loss, from a configuration file's keys, computed in the
parameters' dtype (float32, at the configuration's matmul precision).

Each block: x += Mamba2(RMSNorm(x)), where Mamba2 projects the input to
x, z, B, C and dt; runs a causal depthwise convolution of width ``d_conv``
and SiLU over each of x, B and C; discretizes with dt = softplus(. + bias)
and A = -exp(A_log); runs the SSD scan with the skip term D; gates with
SiLU(z) before an RMSNorm; and projects back. The LM head is untied.

The SSD scan follows the paper's minimal listing (section 6, "SSD
minimal"): within chunks of ``chunk_size`` the quadratic form with the
stable segment sum, across chunks the states passed by the matrix of chunk
decays. That is a different evaluation order from the program's scan over
chunks, so the two agree only as far as the mathematics does.

Departures from the published model, which the program makes and the
configuration file lists: the head is untied; the convolutions carry no
bias. Weights are laid out as the program keeps them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.reference.common import (einsum, fan_in_std,
                                              init_from_table, next_token_ce,
                                              padded_vocab, rmsnorm)


def dims(cfg):
    d = cfg["d_model"]
    d_in = cfg["expand"] * d
    nh = d_in // cfg["headdim"]
    gn = cfg["ngroups"] * cfg["d_state"]
    return d, d_in, nh, gn


def param_table(cfg):
    """Leaf shapes and initial distributions, in the program's layout."""
    if cfg["tie_embeddings"]:
        raise ValueError("the mamba2 reference covers an untied head only")
    d, d_in, nh, gn = dims(cfg)
    L, K, V = cfg["n_layer"], cfg["d_conv"], padded_vocab(cfg["vocab_size"])
    w = lambda fan, out: {"w": ((L, fan, out), "normal", fan_in_std(fan))}
    return {
        "embed": {"table": ((V, d), "normal", 0.02),
                  "head": ((d, V), "normal", fan_in_std(d))},
        "blocks": {"stack": {
            "norm1": {"scale": ((L, d), "ones", None)},
            "mamba": {
                "wx": w(d, d_in), "wz": w(d, d_in), "wB": w(d, gn),
                "wC": w(d, gn), "wdt": w(d, nh),
                "conv_x": ((L, d_in, K), "normal", 0.1),
                "conv_B": ((L, gn, K), "normal", 0.1),
                "conv_C": ((L, gn, K), "normal", 0.1),
                "dt_bias": ((L, nh), "zeros", None),
                "A_log": ((L, nh), "log_linspace", (1.0, 16.0)),
                "Dskip": ((L, nh), "ones", None),
                "gate_norm": {"scale": ((L, d_in), "ones", None)},
                "wo": w(d_in, d),
            },
        }},
        "final_norm": {"scale": ((d,), "ones", None)},
    }


def init_params(key, cfg):
    return init_from_table(key, param_table(cfg))


def _conv(x, w):
    """Causal depthwise convolution: x (B,S,C), w (C,K); output t sees
    inputs t-K+1 .. t."""
    K = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    S = x.shape[1]
    return sum(xp[:, k:k + S, :] * w[:, k] for k in range(K))


def _segsum(a):
    """a (..., T) -> (..., T, T) with [i, j] = a[j+1] + ... + a[i] for
    j <= i and -inf above the diagonal (the stable form of the listing)."""
    T = a.shape[-1]
    x = jnp.broadcast_to(a[..., None], a.shape + (T,))           # x[i, j] = a[i]
    x = jnp.where(jnp.tril(jnp.ones((T, T), bool), k=-1), x, 0.0)
    s = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)


def ssd(X, A, B, C, chunk):
    """X (b,S,h,p) = x*dt, A (b,S,h) = dt*A, B/C (b,S,h,n) -> Y (b,S,h,p)."""
    b, S, h, p = X.shape
    c = S // chunk
    r = lambda t: t.reshape((b, c, chunk) + t.shape[2:])
    X, B, C = r(X), r(B), r(C)
    A = r(A).transpose(0, 3, 1, 2)                                 # b h c l
    Acum = jnp.cumsum(A, axis=-1)
    Ld = jnp.exp(_segsum(A))                                       # b h c l s
    Y_diag = einsum("bclhn,bcshn,bhcls,bcshp->bclhp", C, B, Ld, X)
    decay_states = jnp.exp(Acum[..., -1:] - Acum)
    states = einsum("bclhn,bhcl,bclhp->bchpn", B, decay_states, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(Acum[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    states = einsum("bhzc,bchpn->bzhpn", chunk_decay, states)[:, :-1]
    Y_off = einsum("bclhn,bchpn,bhcl->bclhp", C, states, jnp.exp(Acum))
    return (Y_diag + Y_off).reshape(b, S, h, p)


def _block(cfg, x, p):
    d, d_in, nh, gn = dims(cfg)
    eps = cfg["norm_epsilon"]
    m = p["mamba"]
    Bsz, S, _ = x.shape
    u = rmsnorm(p["norm1"]["scale"], x, eps)
    proj = lambda name: einsum("bsd,de->bse", u, m[name]["w"])
    xs = jax.nn.silu(_conv(proj("wx"), m["conv_x"]))
    Bm = jax.nn.silu(_conv(proj("wB"), m["conv_B"]))
    Cm = jax.nn.silu(_conv(proj("wC"), m["conv_C"]))
    z = proj("wz")
    dt = jax.nn.softplus(proj("wdt") + m["dt_bias"])               # b s h
    A = -jnp.exp(m["A_log"])
    xh = xs.reshape(Bsz, S, nh, cfg["headdim"])
    heads = lambda t: jnp.repeat(
        t.reshape(Bsz, S, cfg["ngroups"], cfg["d_state"]),
        nh // cfg["ngroups"], axis=2)
    y = ssd(xh * dt[..., None], dt * A, heads(Bm), heads(Cm),
            min(cfg["chunk_size"], S))
    y = (y + m["Dskip"][:, None] * xh).reshape(Bsz, S, d_in)
    y = rmsnorm(m["gate_norm"]["scale"], y * jax.nn.silu(z), eps)
    return x + einsum("bse,ed->bsd", y, m["wo"]["w"])


def loss(cfg, params, tokens, labels):
    """Mean next-token cross entropy of (B,S) ``tokens`` against ``labels``;
    layers are recomputed in the backward pass (``jax.checkpoint``)."""
    x = params["embed"]["table"][tokens]
    step = jax.checkpoint(lambda x, p: (_block(cfg, x, p), None))
    x, _ = jax.lax.scan(step, x, params["blocks"]["stack"])
    y = rmsnorm(params["final_norm"]["scale"], x, cfg["norm_epsilon"])
    logits = einsum("bsd,dv->bsv", y, params["embed"]["head"])
    return next_token_ce(logits, labels, cfg["vocab_size"])
