"""Pieces the plain references share: arithmetic in the parameters' own
dtype (float32) at the matmul precision in force, seeded weights from a
table of leaf shapes, RMSNorm and the next-token cross entropy.

Nothing here imports the program under test. The weights of a run are made
here, from the run's seed, and handed both to the program and to the
reference, so neither takes anything the other made.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def einsum(spec, *ops):
    """In the operands' dtype, at the matmul precision in force: the
    harness runs the reference under the configuration's
    ``matmul_precision`` (on a TPU, ``default`` multiplies float32 in one
    bfloat16 pass and ``highest`` at full precision)."""
    return jnp.einsum(spec, *ops)


def seed_key(seed: int):
    """A PRNG key from any whole seed below 2**64: both 32-bit halves are
    folded in, so seeds that differ above bit 31 give different keys."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    k = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def padded_vocab(v: int, multiple: int = 2048) -> int:
    """Rows of the embedding table: the vocabulary rounded up to a multiple
    of 2048, as the program lays it out. The extra rows never carry a token
    and are masked out of the softmax."""
    return ((v + multiple - 1) // multiple) * multiple


def init_from_table(key, table):
    """Materialize a nested dict of leaves from ``table``, whose leaves are
    ``(shape, kind, arg)``: ``normal`` with standard deviation ``arg``,
    ``zeros``, ``ones``, or ``log_linspace``, the log of ``shape[-1]``
    values spaced evenly from ``arg[0]`` to ``arg[1]``, alike in every row.
    Leaf i draws from ``fold_in(key, i)`` in ``jax.tree`` flattening order."""
    is_leaf = lambda x: isinstance(x, tuple) and len(x) == 3 \
        and isinstance(x[1], str)
    leaves, treedef = jax.tree.flatten(table, is_leaf=is_leaf)
    out = []
    for i, (shape, kind, arg) in enumerate(leaves):
        if kind == "normal":
            out.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                         F32) * arg)
        elif kind == "zeros":
            out.append(jnp.zeros(shape, F32))
        elif kind == "ones":
            out.append(jnp.ones(shape, F32))
        elif kind == "log_linspace":
            lo, hi = arg
            row = jnp.log(jnp.linspace(lo, hi, shape[-1], dtype=F32))
            out.append(jnp.broadcast_to(row, shape))
        else:
            raise ValueError(f"unknown init kind {kind!r}")
    return jax.tree.unflatten(treedef, out)


def shapes_from_table(table):
    """The ShapeDtypeStruct tree ``init_from_table`` would make."""
    is_leaf = lambda x: isinstance(x, tuple) and len(x) == 3 \
        and isinstance(x[1], str)
    return jax.tree.map(lambda t: jax.ShapeDtypeStruct(t[0], F32), table,
                        is_leaf=is_leaf)


def rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def next_token_ce(logits, labels, vocab_size):
    """Mean negative log-likelihood of ``labels`` under ``logits`` (B,S,V);
    columns at or past ``vocab_size`` are padding and take no mass."""
    V = logits.shape[-1]
    if V > vocab_size:
        logits = jnp.where(jnp.arange(V) >= vocab_size, -jnp.inf, logits)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def fan_in_std(fan_in: int) -> float:
    return 1.0 / math.sqrt(fan_in)
