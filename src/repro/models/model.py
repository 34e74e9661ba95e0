"""Top-level model API: build(cfg) -> Model with init / loss / prefill / decode.

Batch formats (what the data pipeline and input_specs produce):

* token families (dense/moe/ssm/hybrid):
    ``{"tokens": (B,S) i32, "labels": (B,S) i32}``
* audio (musicgen): the EnCodec frontend is a stub — precomputed frame
    embeddings replace token embeddings 1:1:
    ``{"embeds": (B,S,d) f32, "labels": (B,S) i32}``
* vlm (internvl2): ViT/projector stubbed — patch embeddings prepended:
    ``{"patches": (B,P,d) f32, "tokens": (B,S) i32, "labels": (B,S) i32}``
    (labels cover text positions only).

``loss`` returns mean next-token CE (+ MoE aux). ``prefill`` returns last-pos
logits and the decode cache. ``decode`` consumes one token id per sequence.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models import transformer as T
from repro.models.layers import (AttnCall, embed, init_embed, init_rmsnorm,
                                 padded_vocab, rmsnorm, unembed,
                                 unembed_cross_entropy)


@dataclasses.dataclass
class ModelCallConfig:
    """Runtime (non-parameter) knobs; a §Perf surface."""
    dtype: Any = jnp.bfloat16
    attn_chunk: int = 1024          # KV-chunk for online-softmax long prefill
    dense_attn_max: int = 2048      # use dense attention for S <= this
    remat: bool = True
    use_flash_kernel: bool = False
    mla_absorbed: bool = True       # MLA decode in latent space
    decode_window: int = 0          # ring-buffer decode cache (long_500k)
    use_decode_kernel: bool = False  # fused Pallas decode attention + sampling
    softcap: float = 0.0
    exact_moe: bool = False         # no MoE capacity drops (tests)
    # devices of the mesh the step is built for: the loss's fused LM head
    # runs on one only (layers.head_path)
    devices: int = 1
    # optional residual-stream sharding hook: fn((B,S,d)) -> constrained array.
    # Used by launch/steps.py to pin batch-parallel activations when parameter
    # sharding would otherwise win GSPMD propagation (paper_fsdp mode).
    act_shard: Any = None
    # optional (B,E,C,·) MoE-buffer constraint (scatters propagate weakly)
    moe_shard: Any = None


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    call: ModelCallConfig
    init: Callable            # (key) -> params
    loss: Callable            # (params, batch) -> scalar fp32
    prefill: Callable         # (params, batch) -> (logits_last, cache)
    decode: Callable          # (params, cache, token (B,), pos) -> (logits, cache)
    init_cache: Callable      # (batch, cache_len) -> cache pytree
    # (params, batch, cache_len) -> (logits_last, decode cache at pos=S):
    # prefill whose cache feeds decode directly — no prompt replay.
    prefill_cache: Callable = None
    # (params, cache, token, pos, noise (B,V)) -> (next token (B,), cache):
    # one decode step fused with gumbel-argmax sampling (greedy = zero noise).
    decode_sample: Callable = None
    # (params, batch) -> full-sequence fp32 logits (B, S, V): the pre-CE
    # view of ``loss`` — what the semi-supervised client objectives
    # (core/objectives.py) consume for pseudo-labels / consistency targets.
    logits: Callable = None


def build(cfg: ModelConfig, call: Optional[ModelCallConfig] = None) -> Model:
    call = call or ModelCallConfig()
    dtype = call.dtype

    def init(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "embed": init_embed(k1, cfg),
            "blocks": T.init_stack(k2, cfg),
            "final_norm": init_rmsnorm(cfg.d_model),
        }

    def _attncall(S):
        chunk = call.attn_chunk if S > call.dense_attn_max else 0
        return AttnCall(window=0, softcap=call.softcap, chunk=chunk,
                        use_flash_kernel=call.use_flash_kernel,
                        force_window=call.decode_window,
                        exact_moe=call.exact_moe, moe_shard=call.moe_shard)

    def _residual_input(params, batch):
        """family-specific residual-stream input + label positions."""
        if cfg.family == "audio":
            x = batch["embeds"].astype(dtype)
            labels = batch["labels"]
            return x, labels, 0
        if cfg.family == "vlm":
            tx = embed(params["embed"], batch["tokens"], dtype)
            x = jnp.concatenate([batch["patches"].astype(dtype), tx], axis=1)
            P = batch["patches"].shape[1]
            pad = jnp.full((batch["labels"].shape[0], P), -1, jnp.int32)
            labels = jnp.concatenate([pad, batch["labels"]], axis=1)
            return x, labels, 0
        x = embed(params["embed"], batch["tokens"], dtype)
        return x, batch["labels"], 0

    def _constrain(x):
        return call.act_shard(x) if call.act_shard is not None else x

    def _forward_hidden(params, batch):
        x, labels, _ = _residual_input(params, batch)
        x = _constrain(x)
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        y, _, aux = T.forward(params["blocks"], cfg, x, positions,
                              _attncall(S), dtype, want_cache=False,
                              remat=call.remat)
        return rmsnorm(params["final_norm"], y, cfg.norm_eps), labels, aux

    def loss(params, batch):
        y, labels, aux = _forward_hidden(params, batch)
        return unembed_cross_entropy(params["embed"], y, labels, cfg, dtype,
                                     devices=call.devices) + aux

    def logits(params, batch):
        y = _forward_hidden(params, batch)[0]
        return unembed(params["embed"], y, cfg, dtype).astype(jnp.float32)

    def prefill(params, batch):
        x, _, _ = _residual_input(params, batch)
        S = x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        y, caches, _ = T.forward(params["blocks"], cfg, x, positions,
                                 _attncall(S), dtype, want_cache=True,
                                 remat=False)
        y = rmsnorm(params["final_norm"], y[:, -1:, :], cfg.norm_eps)
        logits = unembed(params["embed"], y, cfg, dtype)
        return logits[:, 0, :], caches

    def init_cache(batch_size, cache_len):
        clen = min(cache_len, call.decode_window) if call.decode_window \
            else cache_len
        return T.init_decode_cache(cfg, batch_size, clen, dtype=jnp.bfloat16)

    def prefill_cache(params, batch, cache_len):
        """Prefill returning (last-pos logits, decode-ready cache).

        Unlike ``prefill`` (whose cache is the raw stacked per-layer output),
        the cache here is in ``init_cache`` layout, populated so decode
        continues at pos = prompt_len — no prompt replay.
        """
        x, _, _ = _residual_input(params, batch)
        B, S = x.shape[0], x.shape[1]
        positions = jnp.arange(S, dtype=jnp.int32)
        y, caches, _ = T.forward(params["blocks"], cfg, x, positions,
                                 _attncall(S), dtype, want_cache=True,
                                 remat=False)
        cache = T.prefill_to_decode_cache(cfg, caches, S,
                                          init_cache(B, cache_len))
        y = rmsnorm(params["final_norm"], y[:, -1:, :], cfg.norm_eps)
        logits = unembed(params["embed"], y, cfg, dtype)
        return logits[:, 0, :], cache

    def _decode_call():
        return AttnCall(window=call.decode_window or 0, softcap=call.softcap,
                        force_window=call.decode_window,
                        use_decode_kernel=call.use_decode_kernel,
                        exact_moe=call.exact_moe, moe_shard=call.moe_shard)

    def decode(params, cache, token, pos):
        """token (B,) int32 ids; pos scalar int32 or (B,) per-slot positions.
        Returns (logits (B,V), cache)."""
        x = embed(params["embed"], token[:, None], dtype)
        y, cache = T.decode(params["blocks"], cfg, x, pos, cache,
                            _decode_call(), dtype,
                            mla_absorbed=call.mla_absorbed)
        y = rmsnorm(params["final_norm"], y, cfg.norm_eps)
        logits = unembed(params["embed"], y, cfg, dtype)
        return logits[:, 0, :], cache

    def decode_sample(params, cache, token, pos, noise):
        """One decode step fused with sampling: next token = argmax over the
        real vocab of logits + ``noise`` ((B,V) fp32; zeros = greedy, gumbel
        draws = categorical). With ``use_decode_kernel`` the unembed matmul
        and the argmax run in one Pallas pass without materialising logits."""
        x = embed(params["embed"], token[:, None], dtype)
        y, cache = T.decode(params["blocks"], cfg, x, pos, cache,
                            _decode_call(), dtype,
                            mla_absorbed=call.mla_absorbed)
        y = rmsnorm(params["final_norm"], y, cfg.norm_eps)[:, 0, :]
        if call.use_decode_kernel:
            from repro.kernels import ops as kops
            if cfg.tie_embeddings:
                table, scale = params["embed"]["table"], cfg.d_model ** -0.5
            else:
                # (V, d) layout for the kernel; a production server would
                # pre-transpose once instead of per step
                table, scale = params["embed"]["head"].T, 1.0
            tok = kops.decode_sample(y, table, noise, scale=scale,
                                     v_real=cfg.vocab_size)
        else:
            logits = unembed(params["embed"], y[:, None, :], cfg, dtype)[:, 0]
            logits = logits.astype(jnp.float32) + noise.astype(jnp.float32)
            V = logits.shape[-1]
            if V > cfg.vocab_size:
                logits = jnp.where(jnp.arange(V) >= cfg.vocab_size, -jnp.inf,
                                   logits)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return tok, cache

    return Model(cfg=cfg, call=call, init=init, loss=loss, logits=logits,
                 prefill=prefill, decode=decode, init_cache=init_cache,
                 prefill_cache=prefill_cache, decode_sample=decode_sample)


# --------------------------------------------------------------------------- #
# input specs (abstract stand-ins for every model input; no allocation)
# --------------------------------------------------------------------------- #


def batch_struct(cfg: ModelConfig, batch: int, seq: int):
    """ShapeDtypeStructs of a *training/prefill* batch for this family."""
    i32 = jnp.int32
    f32 = jnp.float32
    if cfg.family == "audio":
        return {
            "embeds": jax.ShapeDtypeStruct((batch, seq, cfg.d_model), f32),
            "labels": jax.ShapeDtypeStruct((batch, seq), i32),
        }
    if cfg.family == "vlm":
        P = cfg.frontend_tokens
        return {
            "patches": jax.ShapeDtypeStruct((batch, P, cfg.d_model), f32),
            "tokens": jax.ShapeDtypeStruct((batch, seq - P), i32),
            "labels": jax.ShapeDtypeStruct((batch, seq - P), i32),
        }
    return {
        "tokens": jax.ShapeDtypeStruct((batch, seq), i32),
        "labels": jax.ShapeDtypeStruct((batch, seq), i32),
    }


def sample_batch(cfg: ModelConfig, key, batch: int, seq: int):
    """Concrete random batch matching batch_struct (for smoke tests/examples)."""
    structs = batch_struct(cfg, batch, seq)
    out = {}
    for name, s in structs.items():
        key = jax.random.fold_in(key, hash(name) % (2**31))
        if s.dtype == jnp.int32:
            out[name] = jax.random.randint(key, s.shape, 0, cfg.vocab_size,
                                           jnp.int32)
        else:
            out[name] = jax.random.normal(key, s.shape, jnp.float32)
    return out
