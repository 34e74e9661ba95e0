"""One-pass sync average — Pallas TPU kernel.

The averaging server's sync (``engine.build_round_step``) replaces every
client's copy of a leaf by the participation-weighted mean of the copies,

    a = Σ_m w_m · x_m,        x_m ← a   for every client m,

and, for the params, reports the client drift Σ_m ‖x_m − x̄‖² with x̄ the
unweighted mean. In XLA that is five passes over the params (weighted mean,
broadcast-back, drift mean, its division, Σ(x − x̄)²) and two over the
momentum. This kernel reads the ``(M, …)`` client copies once, computes the
average and, for the params, the drift in the same pass.

Two ways out, by where the leaf lives when the sync reads it:

* in place — a leaf ``(M, …, R', C)`` in the default layout is viewed as
  ``(M, R, C)`` (merging the leading dims is a bitcast when the last two
  dims tile to (8, 128): ``tiles``) and the average is written to all M
  slots over x's own buffer (the output aliases the input): 2·M reads and
  writes per element, the least the sync can move.
* layer-major — a layer stack ``(M, L, …)`` is carried through the client
  loop layer-major, ``(L, M, …)`` in memory, because the model scans over
  its layers. The kernel reads that order (a bitcast of ``swapaxes(x, 0,
  1)``) and writes the average once, ``(L, …)``; XLA's broadcast then writes
  it to every client in the state's own layout. Writing all M slots here
  would need a new ``(M, …)`` buffer beside the loop's (the layouts differ,
  so it cannot alias), which costs the round step's peak memory a whole
  leaf; the average costs 1/M of one, as the jnp sync's did.

The grid walks ``(M, tr, tc)`` blocks of about ``BLOCK_BYTES``; the weights
ride as a scalar-prefetch operand (traced under partial participation). The
sums run in client order, as XLA's ``(x * w).sum(axis=0)``: for M = 2 and
w = ½ both products are exact, so the average is bitwise the jnp sync's.
The drift sums into one (8, 128) block that stays resident across the grid.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one (M, tr, tc) fp32 input block: in and out double-buffered with the
# body's temporaries stay inside the default scoped VMEM
BLOCK_BYTES = 2 * 1024 * 1024


def tiles(shape, dtype) -> bool:
    """True iff an ``(M, …)`` client leaf takes the kernel: fp32, at least
    two dims after M, the last two a multiple of (8, 128), so merging the
    leading dims into rows is a bitcast of the tiled layout."""
    return (jnp.dtype(dtype) == jnp.float32 and len(shape) >= 3
            and shape[-2] % 8 == 0 and shape[-1] % 128 == 0)


def _split(n: int, most: int, unit: int) -> int:
    """The block length, a multiple of ``unit``, of the fewest blocks of at
    most ``most`` that cover ``n``, as even as the unit allows."""
    units = pl.cdiv(n, unit)
    n_blocks = pl.cdiv(units, max(1, most // unit))
    return pl.cdiv(units, n_blocks) * unit


def _blocks(M: int, R: int, C: int, block_bytes: int):
    """(tr, tc): lane-dense column blocks of at most ``block_bytes`` for
    eight rows, then as many 8-row groups as fit."""
    tc = _split(C, block_bytes // (M * 8 * 4), 128)
    tr = _split(R, block_bytes // (M * tc * 4), 8)
    return tr, tc


def _kernel(w_ref, x_ref, o_ref, *drift_ref, M, R, C, tr, tc, in_place):
    a = x_ref[0] * w_ref[0]
    for m in range(1, M):
        a = a + x_ref[m] * w_ref[m]
    if drift_ref:
        s = x_ref[0]
        for m in range(1, M):
            s = s + x_ref[m]
        mean = s / M
        sq = (x_ref[0] - mean) ** 2
        for m in range(1, M):
            sq = sq + (x_ref[m] - mean) ** 2
        if R % tr or C % tc:
            # the tail blocks read past the array: drop those lanes
            rows = pl.program_id(1) * tr + jax.lax.broadcasted_iota(
                jnp.int32, (tr, tc), 0)
            cols = pl.program_id(2) * tc + jax.lax.broadcasted_iota(
                jnp.int32, (tr, tc), 1)
            sq = jnp.where((rows < R) & (cols < C), sq, 0.0)
        first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0) \
            & (pl.program_id(2) == 0)

        @pl.when(first)
        def _():
            drift_ref[0][...] = jnp.zeros((8, 128), jnp.float32)

        drift_ref[0][...] += jnp.sum(sq)
    if in_place:
        for m in range(M):
            o_ref[m] = a
    else:
        o_ref[...] = a


@functools.partial(jax.jit, static_argnames=("drift", "layer_major",
                                             "block_bytes", "interpret"))
def sync_average(x, w, *, drift=False, layer_major=False,
                 block_bytes=BLOCK_BYTES, interpret=False):
    """``x`` (M, …) fp32 with ``tiles(x.shape, x.dtype)``; ``w`` (M,) fp32.

    Returns ``(out, drift_sum)``: ``out`` is x's shape with every client
    slot holding Σ_m w_m·x_m; drift_sum is Σ_m ‖x_m − mean_m x_m‖² (fp32
    scalar) when ``drift``, else None. ``layer_major=False`` writes ``out``
    over x's buffer; ``layer_major=True`` (x at least 4-D, its dim 1 the
    layers) reads x layer-major and broadcasts the average in XLA (see the
    module docstring).
    """
    if not tiles(x.shape, x.dtype) or (layer_major and x.ndim < 4):
        raise ValueError(f"sync_average: leaf {x.shape} {x.dtype} does not "
                         f"tile to (8, 128) fp32"
                         + (" behind a layer dim" if layer_major else ""))
    M, C = x.shape[0], x.shape[-1]
    L = x.shape[1] if layer_major else 1
    R = math.prod(x.shape[1 + layer_major:-1])
    tr, tc = _blocks(M, R, C, block_bytes)
    grid = (L, pl.cdiv(R, tr), pl.cdiv(C, tc))
    in_spec = pl.BlockSpec((pl.squeezed, M, tr, tc),
                           lambda l, i, j, w_ref: (l, 0, i, j))
    if layer_major:
        xv = jnp.swapaxes(x, 0, 1).reshape(L, M, R, C)
        out_spec = pl.BlockSpec((pl.squeezed, tr, tc),
                                lambda l, i, j, w_ref: (l, i, j))
        out_view, aliases = (L, R, C), {}
    else:
        xv = x.reshape(1, M, R, C)
        out_spec, out_view, aliases = in_spec, (1, M, R, C), {1: 0}
    out_specs = [out_spec]
    out_shape = [jax.ShapeDtypeStruct(out_view, jnp.float32)]
    if drift:
        out_specs.append(pl.BlockSpec((8, 128),
                                      lambda l, i, j, w_ref: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((8, 128), jnp.float32))
    kern = functools.partial(_kernel, M=M, R=R, C=C, tr=tr, tc=tc,
                             in_place=not layer_major)
    outs = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=[in_spec],
            out_specs=out_specs),
        out_shape=out_shape,
        input_output_aliases=aliases,
        # the drift block accumulates across the whole grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
        name="sync_average",
    )(w.astype(jnp.float32), xv)
    if layer_major:
        out = jnp.broadcast_to(outs[0].reshape(x.shape[1:])[None], x.shape)
    else:
        out = outs[0].reshape(x.shape)
    return out, (outs[1][0, 0] if drift else None)
