"""Fused decode-step kernels — Pallas TPU.

Two kernels cover the decode hot loop's bandwidth-bound spots (DESIGN.md §8):

* ``decode_attention`` — single-query flash decode: one grid cell per
  (batch-slot, kv-head) reads the slot's whole C-deep KV ring plus a
  precomputed additive mask bias (causal/window/ring-validity — computed by
  the caller in O(C) jnp, which keeps the kernel agnostic to traced per-layer
  windows) and produces the attended output for that head group.

* ``decode_sample`` — the logits→token tail: unembed matmul against the
  (V, d) embedding table fused with a running blockwise argmax over vocab
  blocks, so the (B, V) logits are never materialised in HBM. ``noise`` is an
  additive (B, V) fp32 operand: zeros = greedy argmax; Gumbel draws =
  categorical sampling (the Gumbel-max trick — bitwise what
  ``jax.random.categorical`` computes).

Both kernel bodies source their math from ``kernels/ref.py`` (the
``fused_step_flat`` contract pattern), and the shared math uses
elementwise-mul + axis-sum contractions rather than ``jnp.dot`` so the
per-cell kernel blocks and the batched oracle reduce in the same order —
that is what makes fused == oracle *bitwise* on every backend (a dot-general
would pick shape-dependent accumulation orders; see tests/test_serve.py).

VMEM note: ``decode_attention`` holds one slot's full KV in VMEM — C·D·8
bytes fp32 per (k, v); fine up to the LONG_DECODE_WINDOW ring (8192·64·4·2
≈ 4 MiB) but not for an unwindowed 500k cache — long contexts must decode
through ``decode_window``. ``decode_sample`` sizes its vocab block so one
table tile stays within ``TABLE_BLOCK_BYTES`` (``vocab_block``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as kref

NEG_INF = -1e30
TABLE_BLOCK_BYTES = 4 << 20   # one buffer of decode_sample's table tile


# --------------------------------------------------------------------------- #
# single-query decode attention
# --------------------------------------------------------------------------- #


def _attn_kernel(q_ref, k_ref, v_ref, b_ref, o_ref, *, softcap):
    o_ref[...] = kref.decode_attention_math(q_ref[...], k_ref[...],
                                            v_ref[...], b_ref[0], softcap)


@functools.partial(jax.jit, static_argnames=("softcap", "interpret"))
def decode_attention(q, k, v, bias, *, softcap=0.0, interpret=False):
    """q (B,H,D), k/v (B,C,Hk,D/Dv) cache layout, bias (B,C) fp32 additive
    mask -> (B,H,Dv) fp32.

    The cache is read head-major, (B,Hk,C,D): one grid cell then holds a
    (C, D) slab, which meets Mosaic's tiling rule (the last two block dims
    must be multiples of (8, 128) or whole dims) for any Hk, where a (1, D)
    slice of the (Hk, D) minor dims does not."""
    B, H, D = q.shape
    C, Hk = k.shape[1], k.shape[2]
    Dv = v.shape[3]
    rep = H // Hk
    qr = q.reshape(B, Hk, rep, D)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)
    kern = functools.partial(_attn_kernel, softcap=softcap)
    cell = lambda b, h: (b, h, 0, 0)
    out = pl.pallas_call(
        kern,
        grid=(B, Hk),
        in_specs=[
            pl.BlockSpec((None, None, rep, D), cell),
            pl.BlockSpec((None, None, C, D), cell),
            pl.BlockSpec((None, None, C, Dv), cell),
            pl.BlockSpec((None, 1, C), lambda b, h: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, rep, Dv), cell),
        out_shape=jax.ShapeDtypeStruct((B, Hk, rep, Dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(qr, kh, vh, bias.reshape(B, 1, C))
    return out.reshape(B, H, Dv)


# --------------------------------------------------------------------------- #
# fused unembed + sampling tail
# --------------------------------------------------------------------------- #


def _sample_kernel(y_ref, t_ref, n_ref, best_ref, arg_ref, *, blk, v_real,
                   scale):
    j = pl.program_id(0)
    logits = kref.decode_sample_math(y_ref[...], t_ref[...], n_ref[...],
                                     scale)                       # (B, blk)
    vidx = j * blk + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    logits = jnp.where(vidx < v_real, logits, NEG_INF)
    m = logits.max(axis=1)                                        # (B,)
    a = (j * blk + jnp.argmax(logits, axis=1)).astype(jnp.int32)

    @pl.when(j == 0)
    def _init():
        best_ref[0] = m
        arg_ref[0] = a

    @pl.when(j > 0)
    def _update():
        prev = best_ref[0]
        upd = m > prev            # strict: earlier block wins ties, like argmax
        arg_ref[0] = jnp.where(upd, a, arg_ref[0])
        best_ref[0] = jnp.where(upd, m, prev)


def vocab_block(d: int, itemsize: int, *, max_block=2048,
                budget=TABLE_BLOCK_BYTES) -> int:
    """Largest power-of-two vocab block up to ``max_block`` whose (block, d)
    table tile fits ``budget`` bytes. The tile is double-buffered, so at
    d=896 fp32 a 2048 block (7.3 MB, twice) overruns v5e's 16 MiB scoped
    VMEM where 1024 fits."""
    block = max_block
    while block > 8 and block * d * itemsize > budget:
        block //= 2
    return block


@functools.partial(jax.jit, static_argnames=("scale", "v_real", "block",
                                             "interpret"))
def decode_sample(y, table, noise, *, scale, v_real, block=None,
                  interpret=False):
    """y (B,d) final hidden, table (V,d), noise (B,V) fp32 -> token ids (B,).

    token[b] = argmax_v<v_real (y[b]·table[v])*scale + noise[b,v]. The vocab
    grid is sequential ("arbitrary"): a running (best, arg) pair lives in the
    output blocks across vocab steps. ``block`` defaults to ``vocab_block``;
    the token does not depend on it (first-index ties either way).
    """
    B, d = y.shape
    V = table.shape[0]
    if block is None:
        block = vocab_block(d, table.dtype.itemsize)
    block = min(block, V)
    assert V % block == 0, (V, block)
    kern = functools.partial(_sample_kernel, blk=block, v_real=v_real,
                             scale=scale)
    _, arg = pl.pallas_call(
        kern,
        grid=(V // block,),
        in_specs=[
            pl.BlockSpec((B, d), lambda j: (0, 0)),
            pl.BlockSpec((block, d), lambda j: (j, 0)),
            pl.BlockSpec((B, block), lambda j: (0, j)),
        ],
        out_specs=[pl.BlockSpec((1, B), lambda j: (0, 0)),
                   pl.BlockSpec((1, B), lambda j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, B), jnp.float32),
                   jax.ShapeDtypeStruct((1, B), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(y, table, noise)
    return arg[0]
