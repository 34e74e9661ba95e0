"""Fused local-step kernels — Pallas TPU.

Two generations live here:

* ``scaled_update_flat`` — the original fused SAVIC step on one flat fp32
  array: ``m' = β₁m + g``, ``D̂ = clip(mag(d))``, ``p' = p − γ m'/D̂``.  Kept as
  the public per-leaf kernel (``ops.scaled_update``) and as the "pre-PR
  kernel path" baseline in ``benchmarks/run.py --only kernels``.

* ``fused_step_flat`` — the flat-buffer kernel FAMILY (DESIGN.md §7): the
  whole generic-scaling local step of the paper's unified Assumption-4 rule
  in ONE pass over the per-client flat buffer ``(M, n)``.  Fuses the D̂
  update — rule-2 squared EMA (Adam/RMSProp), rule-3 linear EMA (OASIS),
  AdaGrad accumulate, β_t const or Adam-debias (the per-client β_t rides as
  a scalar prefetch) — together with the momentum + scaled parameter
  update, for every ``PrecondConfig`` kind including identity.  Per element
  that is 4–5 HBM reads (p, m, g, d[, h]) + 3 writes (p', m', d') where the
  per-leaf path paid 6+ reads / 4 writes across three launches (momentum
  pass, per-leaf kernel, separate D̂ EMA pass).  The grid walks lane blocks
  of ``(M, blk)``, so one ``pallas_call`` covers every client's step;
  per-client scalars (debias β_t, grad-clip scale ``s``) are scalar-prefetch
  operands broadcast to an ``(M, 1)`` column.

The kernel body calls ``ref.fused_step_math`` — the pure-jnp oracle is the
single source of truth for the formula, and the engine's unfused tree path is
pinned bit-identical to it (tests/test_fused_step.py).

Padding contract (audited per rule, pinned at n % BLOCK ∈ {0, 1, BLOCK−1}):

* ``fused_step_flat`` does NOT pad.  The grid's tail block is partial and
  Pallas handles it implicitly (reads of the out-of-range lanes see runtime
  padding, their stores are dropped) — safe for EVERY rule because the step
  is elementwise: no value crosses lanes, and tail lanes never reach an
  output.  This matters in the hot loop: an explicit ``jnp.pad`` before a
  custom call materializes a full copy of every operand (and ``[:n]`` a copy
  of every output) per local step.
* the legacy ``scaled_update_flat`` keeps its explicit pads (it predates the
  flat-buffer path and is the benchmark's pre-PR baseline): p/m/g → 0 and
  d → 1.0, which keeps D̂ = 1 in the pad under BOTH the rule-2 √d and the
  rule-3 |d| magnitudes, so pad lanes stay finite for every (clip, α ≥ 0).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ref as kref

BLOCK = 8 * 128 * 16


def _block_for(n: int, block: int) -> int:
    """Lane-aligned block: small arrays get one 128-multiple block instead of
    padding all the way to BLOCK (identical results — elementwise kernel)."""
    aligned = -(-n // 128) * 128
    return min(block, aligned)


def _pad1(x, n_pad, value):
    npad = n_pad - x.shape[-1]
    if not npad:
        return x
    widths = [(0, 0)] * (x.ndim - 1) + [(0, npad)]
    return jnp.pad(x, widths, constant_values=value)


# --------------------------------------------------------------------------- #
# original per-leaf kernel (rule-4 clip "max" only, D fixed)
# --------------------------------------------------------------------------- #


def _kernel(p_ref, m_ref, g_ref, d_ref, po_ref, mo_ref, *, gamma, beta1,
            alpha, squared):
    m = beta1 * m_ref[...] + g_ref[...]
    d = d_ref[...]
    mag = jnp.sqrt(d) if squared else jnp.abs(d)
    dhat = jnp.maximum(alpha, mag)
    po_ref[...] = p_ref[...] - gamma * m / dhat
    mo_ref[...] = m


@functools.partial(jax.jit,
                   static_argnames=("gamma", "beta1", "alpha", "squared",
                                    "interpret"))
def scaled_update_flat(p, m, g, d, *, gamma, beta1, alpha, squared=True,
                       interpret=False):
    """Flat fp32 arrays (n,) -> (p', m'). Pads to a lane-aligned block
    internally (see the module padding contract: p/m/g → 0, d → 1.0 keeps
    D̂ = 1 in the pad for BOTH the rule-2 √d and the rule-3 |d| magnitude)."""
    n = p.shape[0]
    blk = _block_for(n, BLOCK)
    n_pad = -(-n // blk) * blk
    p, m, g = (_pad1(x, n_pad, 0) for x in (p, m, g))
    d = _pad1(d, n_pad, 1.0)
    grid = (n_pad // blk,)
    spec = pl.BlockSpec((blk,), lambda i: (i,))
    kern = functools.partial(_kernel, gamma=gamma, beta1=beta1, alpha=alpha,
                             squared=squared)
    po, mo = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[spec] * 4,
        out_specs=[spec] * 2,
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype)] * 2,
        interpret=interpret,
    )(p, m, g, d)
    return po[:n], mo[:n]


# --------------------------------------------------------------------------- #
# fused flat-buffer kernel family: one pass per local step, every D̂ rule
# --------------------------------------------------------------------------- #


def _per_client(ref, M):
    """(M,) scalar-prefetch operand -> (M, 1) column broadcasting over lanes."""
    row = jax.lax.broadcasted_iota(jnp.int32, (M, 1), 0)
    col = jnp.full((M, 1), ref[0], ref.dtype)
    for i in range(1, M):
        col = jnp.where(row == i, ref[i], col)
    return col


def _fused_kernel(b_ref, s_ref, *refs, n_in, M, gamma, beta1, weight_decay,
                  alpha, beta2, kind, clip, schedule, update_d, has_d,
                  has_h, clipped, prefetch_beta):
    it = iter(refs[:n_in])
    p, m, g = next(it)[...], next(it)[...], next(it)[...]
    d = next(it)[...] if has_d else None
    h = next(it)[...] if has_h else None
    b = _per_client(b_ref, M) if prefetch_beta else None
    s = _per_client(s_ref, M) if clipped else None
    p_new, m_new, d_new = kref.fused_step_math(
        p, m, g, d, h, b, s, gamma=gamma, beta1=beta1,
        weight_decay=weight_decay, alpha=alpha, beta2=beta2, kind=kind,
        clip=clip, schedule=schedule, update_d=update_d)
    outs = refs[n_in:]
    outs[0][...] = p_new
    outs[1][...] = m_new
    if update_d:
        outs[2][...] = d_new


@functools.partial(jax.jit,
                   static_argnames=("gamma", "beta1", "weight_decay", "alpha",
                                    "beta2", "kind", "clip", "schedule",
                                    "update_d", "block", "interpret"))
def fused_step_flat(p, m, g, d=None, h=None, t=None, s=None, *, gamma, beta1,
                    weight_decay=0.0, alpha, beta2=0.999, kind, clip="max",
                    schedule="const", update_d=False, block=BLOCK,
                    interpret=False):
    """One fused local step on per-client flat buffers.

    Shapes: ``p/m/g`` (M, n) fp32; ``d`` (M, n) for local scaling, (n,) for
    global (client-shared D̂), None for the identity kind; ``h`` (M, n)
    external stat (Hutchinson kinds) or None for the in-kernel grad² stat;
    ``t`` (M,) i32 per-client step counters (required for the debias
    schedule); ``s`` (M,) f32 per-client grad-clip scales or None.

    Each grid step covers all M clients over one lane block: ``(M, blk)`` is
    the tiling Mosaic accepts for any M (a block's second-minor dim must be a
    multiple of 8 or the whole dim). The debias factor β_t is computed per
    client here in XLA, by the tree path's own expression
    (``ref.debias_beta``), and enters as a scalar-prefetch operand: the
    kernel never raises to a power (Mosaic cannot lower ``powf``).

    Returns ``(p', m', d')`` with ``d'`` None unless ``update_d`` (which
    requires a local, (M, n)-shaped ``d``).
    """
    M, n = p.shape
    has_d = d is not None
    has_h = h is not None
    global_d = has_d and d.ndim == 1
    clipped = s is not None
    if update_d and (not has_d or global_d):
        raise ValueError("update_d needs a per-client (M, n) d buffer")
    beta = kref.debias_beta(t, kind=kind, beta2=beta2, schedule=schedule,
                            update_d=update_d)
    prefetch_beta = beta is not None

    # one block spans every client row; keep it near BLOCK elements of
    # (8-row padded) VMEM per buffer as M grows
    rows = -(-M // 8) * 8
    blk = _block_for(n, max(128, (block * 8 // rows) // 128 * 128))
    # no explicit padding: the tail block is partial and Pallas masks it
    # (see the module padding contract) — an explicit pad would copy every
    # operand per local step
    operands = [p, m, g]
    row_spec = pl.BlockSpec((M, blk), lambda j, b_ref, s_ref: (0, j))
    in_specs = [row_spec] * 3
    if has_d:
        operands.append(d.reshape(1, n) if global_d else d)
        in_specs.append(pl.BlockSpec((1, blk), lambda j, b_ref, s_ref: (0, j))
                        if global_d else row_spec)
    if has_h:
        operands.append(h)
        in_specs.append(row_spec)
    if beta is None:
        beta = jnp.zeros((M,), jnp.float32)
    if s is None:
        s = jnp.ones((M,), jnp.float32)

    n_out = 3 if update_d else 2
    kern = functools.partial(
        _fused_kernel, n_in=len(operands), M=M, gamma=gamma, beta1=beta1,
        weight_decay=weight_decay, alpha=alpha, beta2=beta2, kind=kind,
        clip=clip, schedule=schedule, update_d=update_d, has_d=has_d,
        has_h=has_h, clipped=clipped, prefetch_beta=prefetch_beta)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(-(-n // blk),),
        in_specs=in_specs,
        out_specs=[row_spec] * n_out,
    )
    outs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((M, n), jnp.float32)] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(beta, s, *operands)
    po, mo = outs[0], outs[1]
    do = outs[2] if update_d else None
    return po, mo, do
