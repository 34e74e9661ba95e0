"""Plain reference of the Qwen2 decoder (arXiv:2407.10671) and its
next-token loss, from a configuration file's keys, computed in the
parameters' dtype (float32, at the configuration's matmul precision).

Each block: x += Attn(RMSNorm(x)); x += SwiGLU(RMSNorm(x)). Attention is
grouped-query (``num_key_value_heads`` shared by the query heads in order),
with biases on q, k and v, rotary embeddings of the rotate-half form at
``rope_theta``, causal softmax. The LM head is the transposed embedding
table. One departure from the published model, which the program makes and
the configuration file lists: the tied head's logits are scaled by
``hidden_size ** -0.5``.

Weights are laid out as the program keeps them (layers stacked on a leading
axis, projections head-major), so one seeded tree feeds both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.chip.reference.common import (F32, einsum, fan_in_std,
                                              init_from_table, next_token_ce,
                                              padded_vocab, rmsnorm)


def dims(cfg):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return (d, h, cfg["num_key_value_heads"], d // h,
            cfg["intermediate_size"], cfg["num_hidden_layers"],
            cfg["vocab_size"])


def param_table(cfg):
    """Leaf shapes and initial distributions, in the program's layout."""
    if not cfg["tie_word_embeddings"]:
        raise ValueError("the qwen2 reference covers tied embeddings only")
    d, h, hk, hd, f, L, V = dims(cfg)
    n = lambda shape, fan: (shape, "normal", fan_in_std(fan))

    def proj(heads):
        return {"w": n((L, d, heads, hd), d), "b": ((L, heads, hd), "zeros",
                                                    None)}

    return {
        "embed": {"table": ((padded_vocab(V), d), "normal", 0.02)},
        "blocks": {"stack": {
            "norm1": {"scale": ((L, d), "ones", None)},
            "norm2": {"scale": ((L, d), "ones", None)},
            "attn": {"wq": proj(h), "wk": proj(hk), "wv": proj(hk),
                     "wo": {"w": n((L, h, hd, d), h * hd)}},
            "ffn": {"wg": {"w": n((L, d, f), d)},
                    "wu": {"w": n((L, d, f), d)},
                    "wd": {"w": n((L, f, d), f)}},
        }},
        "final_norm": {"scale": ((d,), "ones", None)},
    }


def init_params(key, cfg):
    return init_from_table(key, param_table(cfg))


def _rope(x, theta):
    """Rotate-half rotary embedding of x (B,S,H,D) at positions 0..S-1."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]       # (S, D/2)
    c = jnp.cos(ang).astype(x.dtype)[None, :, None, :]
    s = jnp.sin(ang).astype(x.dtype)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _block(cfg, x, p):
    d, h, hk, hd, f, L, V = dims(cfg)
    eps = cfg["rms_norm_eps"]
    B, S, _ = x.shape
    a = p["attn"]
    u = rmsnorm(p["norm1"]["scale"], x, eps)
    q = einsum("bsd,dhk->bshk", u, a["wq"]["w"]) + a["wq"]["b"]
    k = einsum("bsd,dhk->bshk", u, a["wk"]["w"]) + a["wk"]["b"]
    v = einsum("bsd,dhk->bshk", u, a["wv"]["w"]) + a["wv"]["b"]
    q = _rope(q, cfg["rope_theta"]).reshape(B, S, hk, h // hk, hd)
    k = _rope(k, cfg["rope_theta"])
    s = einsum("bqgrd,bkgd->bgrqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = einsum("bgrqk,bkgd->bqgrd", w, v).reshape(B, S, h, hd)
    x = x + einsum("bshk,hkd->bsd", o, a["wo"]["w"])
    u = rmsnorm(p["norm2"]["scale"], x, eps)
    m = p["ffn"]
    g = einsum("bsd,df->bsf", u, m["wg"]["w"])
    up = einsum("bsd,df->bsf", u, m["wu"]["w"])
    return x + einsum("bsf,fd->bsd", jax.nn.silu(g) * up, m["wd"]["w"])


def loss(cfg, params, tokens, labels):
    """Mean next-token cross entropy of (B,S) ``tokens`` against ``labels``.
    Layers are recomputed in the backward pass (``jax.checkpoint``) so that
    long sequences fit beside the optimizer state."""
    d = cfg["hidden_size"]
    x = params["embed"]["table"][tokens]
    step = jax.checkpoint(lambda x, p: (_block(cfg, x, p), None))
    x, _ = jax.lax.scan(step, x, params["blocks"]["stack"])
    y = rmsnorm(params["final_norm"]["scale"], x, cfg["rms_norm_eps"])
    logits = einsum("bsd,vd->bsv", y, params["embed"]["table"]) * d ** -0.5
    return next_token_ce(logits, labels, cfg["vocab_size"])
