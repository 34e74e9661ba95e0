"""Blockwise (flash) causal attention — Pallas TPU kernel.

Layout: q (B, H, S, D), k/v (B, Hk, S, D) — GQA handled in the BlockSpec
index map (kv head = q head // rep), so no repeated-KV materialization.

Grid = (B, H, nq, nk) with the kv dim innermost/sequential ("arbitrary"):
running (m, l, acc) live in VMEM scratch and persist across the kv loop;
the output block is written on the last kv step. Causal + optional sliding
window handled by masking; fully-masked kv blocks are skipped with pl.when
(upper-triangle blocks cost nothing).

Block sizes default to (128, 128) — MXU-aligned; VMEM working set per step is
q(128·D) + k(128·D) + v(128·D) + scores(128·128) ≈ 0.4 MiB at D=128 fp32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq, bk, nk, window, softcap, scale):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk
    diag_ok = k_start <= q_start + bq - 1           # any unmasked causal pair
    win_ok = True
    if window:
        win_ok = (q_start - (k_start + bk - 1)) < window

    @pl.when(jnp.logical_and(diag_ok, win_ok))
    def _body():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        s = q @ k.T                                  # (bq, bk)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = cols <= rows
        if window:
            mask &= (rows - cols) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                          # (bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr \
            + p @ v_ref[0, 0].astype(jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "bq", "bk", "interpret"))
def flash_attention_bhsd(q, k, v, *, causal=True, window=0, softcap=0.0,
                         bq=128, bk=128, interpret=False):
    """q (B,H,S,D), k/v (B,Hk,S,D) -> (B,H,S,D). Causal only (decoder LMs)."""
    assert causal, "only causal attention is implemented"
    B, H, S, D = q.shape
    Hk = k.shape[1]
    rep = H // Hk
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    nq, nk = S // bq, S // bk
    grid = (B, H, nq, nk)

    kern = functools.partial(_kernel, bq=bq, bk=bk, nk=nk, window=window,
                             softcap=softcap, scale=D ** -0.5)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik: (b, h // rep, ik, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, iq, ik: (b, h // rep, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max m
            pltpu.VMEM((bq, 1), jnp.float32),    # running sum l
            pltpu.VMEM((bq, D), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
