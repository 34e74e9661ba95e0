"""The fused local-step kernel against its pure-jnp oracle.

``kernels/scaled_update.py:fused_step_flat`` (through
``kernels.ops.fused_local_step``) updates D̂, momentum and parameters of
``(M, n)`` blocks in one Pallas pass. No engine path calls it today; it is
kept as the candidate for a per-leaf scaled local step (DESIGN.md §7).

  * the kernel matches ``ref.fused_step_ref`` for every PrecondConfig kind ×
    β_t schedule × rule-4 clip, local/global/identity D, external
    (Hutchinson) and in-kernel grad² stats — including negative rule-3
    (OASIS) D state;
  * the per-rule padding contract at n % BLOCK ∈ {0, 1, BLOCK−1} — sliced
    outputs bitwise, padded lanes never poison them (deterministic and
    hypothesis via the _hypothesis_compat shim).
"""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.kernels import ops, ref
from repro.kernels import scaled_update as su


# --------------------------------------------------------------------------- #
# kernel family vs the pure-jnp oracle (jit-vs-jit: FMA-consistent)
# --------------------------------------------------------------------------- #


def _kernel_buffers(M=3, n=300, seed=0):
    k = jax.random.key(seed)
    p, m, g = (jax.random.normal(jax.random.fold_in(k, i), (M, n))
               for i in range(3))
    d_signed = jax.random.uniform(jax.random.fold_in(k, 3), (M, n),
                                  minval=-2.0, maxval=2.0)
    h = jax.random.normal(jax.random.fold_in(k, 4), (M, n))  # negative ok
    t = jnp.array([0, 3, 7], jnp.int32)[:M]
    s = jnp.array([1.0, 0.4, 0.9], jnp.float32)[:M]
    return p, m, g, d_signed, h, t, s


@pytest.mark.parametrize("kind,schedule,clip", list(itertools.product(
    ("adam", "rmsprop", "adagrad", "oasis", "adahessian"),
    ("const", "debias"), ("max", "add"))))
def test_kernel_matrix_local_vs_oracle(kind, schedule, clip):
    """Full kernel-level matrix, local D update: kernel == oracle to ≤ 1 ulp.
    OASIS runs on SIGNED d (the |d| magnitude path); Hutchinson kinds take
    the external stat operand, the Adam family the in-kernel grad² stat.

    Tolerance note: this compares two SEPARATELY compiled programs (the
    interpret-mode grid loop vs a plain jit of the oracle), where XLA:CPU may
    contract multiply-adds into FMAs differently — a 1-ulp effect.  The
    padding tests below pin the kernel bitwise against the oracle where
    contraction agrees."""
    p, m, g, d_signed, h, t, s = _kernel_buffers()
    hutch = kind in ("oasis", "adahessian")
    d = d_signed if kind == "oasis" else jnp.abs(d_signed)
    hstat = h if hutch else None
    kw = dict(gamma=0.05, beta1=0.9, alpha=1e-2, beta2=0.99, kind=kind,
              clip=clip, schedule=schedule, update_d=True, weight_decay=0.01)
    po, mo, do = ops.fused_local_step(p, m, g, d, hstat, t, s, **kw)
    pr, mr, dr = jax.jit(
        lambda *a: ref.fused_step_ref(*a, **kw))(p, m, g, d, hstat, t, s)
    np.testing.assert_allclose(np.asarray(po), np.asarray(pr), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(mo), np.asarray(mr), rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(do), np.asarray(dr), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("kind", ["adam", "oasis", "identity"])
def test_kernel_global_and_identity_vs_oracle(kind):
    """Global (client-shared (n,)) D and the identity kind: no D output, one
    kernel covers all clients."""
    p, m, g, d_signed, h, t, s = _kernel_buffers()
    d = None if kind == "identity" else \
        (d_signed[0] if kind == "oasis" else jnp.abs(d_signed[0]))
    kw = dict(gamma=0.05, beta1=0.9, alpha=1e-2, beta2=0.99, kind=kind,
              clip="max", schedule="const", update_d=False)
    po, mo, do = ops.fused_local_step(p, m, g, d, None, None, s, **kw)
    pr, mr, dr = jax.jit(
        lambda *a: ref.fused_step_ref(*a, **kw))(p, m, g, d, None, None, s)
    assert do is None and dr is None
    np.testing.assert_array_equal(np.asarray(po), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(mo), np.asarray(mr))


def test_kernel_rejects_bad_modes():
    p, m, g, d_signed, h, t, s = _kernel_buffers()
    with pytest.raises(ValueError):
        ops.fused_local_step(p, m, g, None, None, t, None, gamma=0.1,
                             beta1=0.9, alpha=1e-2, kind="adam",
                             update_d=True)
    with pytest.raises(ValueError):
        ops.fused_local_step(p, m, g, jnp.abs(d_signed), None, None, None,
                             gamma=0.1, beta1=0.9, alpha=1e-2, kind="adam",
                             schedule="debias", update_d=True)


# --------------------------------------------------------------------------- #
# padding contract at n % BLOCK ∈ {0, 1, BLOCK−1}
# --------------------------------------------------------------------------- #


BLK = 128   # exercise the boundary cheaply via an explicit small block


@pytest.mark.parametrize("n", [BLK, 2 * BLK, BLK + 1, 2 * BLK - 1])
@pytest.mark.parametrize("kind", ["adam", "oasis", "adagrad"])
def test_fused_padding_boundaries(n, kind):
    """Outputs are bitwise the oracle's at every block-boundary residue, per
    rule — incl. the OASIS |d| path on signed state. The kernel pads nothing
    (Pallas masks the partial tail block), so the implicitly-padded tail
    lanes must never leak NaN/Inf into real outputs."""
    M = 2
    k = jax.random.key(n * 7 + len(kind))
    p, m, g = (jax.random.normal(jax.random.fold_in(k, i), (M, n))
               for i in range(3))
    d = jax.random.uniform(jax.random.fold_in(k, 3), (M, n), minval=-2.0,
                           maxval=2.0)
    if kind != "oasis":
        d = jnp.abs(d)
    h = jax.random.normal(jax.random.fold_in(k, 4), (M, n)) \
        if kind == "oasis" else None
    t = jnp.zeros((M,), jnp.int32)
    kw = dict(gamma=0.05, beta1=0.9, alpha=1e-2, beta2=0.99, kind=kind,
              clip="max", schedule="debias", update_d=True)
    po, mo, do = su.fused_step_flat(p, m, g, d, h, t, None, block=BLK,
                                    interpret=True, **kw)
    pr, mr, dr = jax.jit(
        lambda *a: ref.fused_step_ref(*a, **kw))(p, m, g, d, h, t, None)
    np.testing.assert_array_equal(np.asarray(po), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(mo), np.asarray(mr))
    np.testing.assert_array_equal(np.asarray(do), np.asarray(dr))
    for out in (po, mo, do):
        assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("n", [BLK, BLK + 1, 2 * BLK - 1])
@pytest.mark.parametrize("squared", [True, False])
def test_scaled_update_flat_padding_boundaries(n, squared):
    """The original per-leaf kernel under the audited padding (d → 1.0 keeps
    D̂ = 1 in the pad for BOTH √d and |d| magnitudes) at the same residues."""
    k = jax.random.key(n + squared)
    p, m, g = (jax.random.normal(jax.random.fold_in(k, i), (n,))
               for i in range(3))
    d = jax.random.uniform(jax.random.fold_in(k, 3), (n,), minval=-1.5,
                           maxval=1.5)
    if squared:
        d = jnp.abs(d)
    kw = dict(gamma=0.1, beta1=0.9, alpha=1e-3, squared=squared)
    po, mo = ops.scaled_update(p, m, g, d, **kw)
    pr, mr = jax.jit(
        lambda *a: ref.scaled_update_ref(*a, **kw))(p, m, g, d)
    np.testing.assert_array_equal(np.asarray(po), np.asarray(pr))


@given(st.integers(min_value=1, max_value=300), st.integers(0, 50))
@settings(max_examples=15, deadline=None)
def test_fused_padding_property(n, seed):
    """Any n (ragged vs the 128-lane block) comes back bitwise — the
    implicit tail-block masking holds for arbitrary residues."""
    k = jax.random.key(seed)
    M = 2
    p, m, g = (jax.random.normal(jax.random.fold_in(k, i), (M, n))
               for i in range(3))
    d = jnp.abs(jax.random.normal(jax.random.fold_in(k, 3), (M, n))) + 0.1
    kw = dict(gamma=0.05, beta1=0.9, alpha=1e-2, beta2=0.99, kind="rmsprop",
              clip="max", schedule="const", update_d=True)
    po, mo, do = su.fused_step_flat(p, m, g, d, None, None, None, block=BLK,
                                    interpret=True, **kw)
    pr, mr, dr = jax.jit(
        lambda *a: ref.fused_step_ref(*a, **kw))(p, m, g, d, None, None, None)
    np.testing.assert_array_equal(np.asarray(po), np.asarray(pr))
    np.testing.assert_array_equal(np.asarray(do), np.asarray(dr))
