"""Heterogeneous & asynchronous rounds — the differential test harness.

Locks down ClientLoopSpec.local_steps + AsyncSpec (DESIGN.md §5):
  * differential pinning: an explicitly-uniform H_m vector plus a zero-depth
    staleness buffer is bit-identical to the pre-PR engine snapshot
    (tests/_reference_engine.py) for all six METHODS — the same discipline
    tests/test_compression.py applies to the compression layer;
  * the masked heterogeneous client loop equals a per-client Python-loop
    oracle (plain SGD and heavy-ball clients), including the per-client
    final-step loss metric;
  * uniform-but-truncated H_m equals the plain engine on a truncated batch;
  * staleness weights normalize to 1 for every (B, weighting, round), B=1
    reduces to plain delta averaging, and the buffered engine matches a
    Python FIFO oracle — alone and composed with heterogeneous H_m;
  * systems-heterogeneity models in data/federated.py (step times, budgeted
    H_m, simulated round times);
  * launch-layer threading: buffer sharding, het metadata;
  * spec validation — deterministic versions plus hypothesis variants via
    _hypothesis_compat.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _reference_engine as ref_engine
from _hypothesis_compat import given, settings, st
from repro.core import engine
from repro.data import QuadraticLoader, QuadraticProblem
from repro.data import federated as fed


@pytest.fixture(scope="module")
def problem():
    return QuadraticProblem.make(d=24, M=4, mu=0.5, L=5.0, sigma=0.3, seed=0)


def _quad_loss(problem):
    Q = jnp.asarray(problem.Q, jnp.float32)
    b = jnp.asarray(problem.b, jnp.float32)

    def loss(params, micro):
        x = params["x"]
        return 0.5 * (x - b[0]) @ Q[0] @ (x - b[0]) + micro["z"] @ x

    return loss


def _run(problem, build_round_step, init_state, spec, rounds=4, H=3, seed=0,
         n_clients=4, collect=False):
    loss = _quad_loss(problem)
    step = jax.jit(build_round_step(loss, spec))
    state = init_state(jax.random.PRNGKey(0),
                       lambda k: {"x": jnp.zeros(24)}, spec, n_clients)
    loader = QuadraticLoader(problem, seed=seed)
    key = jax.random.PRNGKey(seed + 1)
    traj = []
    for _ in range(rounds):
        key, k = jax.random.split(key)
        state, met = step(state, jax.tree.map(jnp.asarray,
                                              loader.round_batch(H)), k)
        if collect:
            traj.append(np.asarray(state["params"]["x"][0]))
    return (state, met, traj) if collect else (state, met)


MS_KW = dict(gamma=0.01, alpha=1e-2, eta_l=0.01, eta=0.05)


# --------------------------------------------------------------------------- #
# differential: uniform H_m + no buffer == pre-PR engine, bitwise, 6 methods
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("method", engine.METHODS)
def test_uniform_hm_no_buffer_bit_identical_to_prepr_engine(problem, method):
    """An explicitly-threaded uniform H_m vector (every client = the batch's
    H) and buffer_rounds=0 short-circuit to the exact pre-heterogeneity
    program: trajectories agree BITWISE with the verbatim engine snapshot."""
    H, M = 3, 4
    spec_new = engine.method_spec(method, **MS_KW, local_steps=(H,) * M,
                                  async_buffer=0)
    assert spec_new.sync.asynchrony.is_identity()
    spec_ref = ref_engine.method_spec(method, **MS_KW)
    st_new, met_new = _run(problem, engine.build_round_step,
                           engine.init_state, spec_new, H=H, n_clients=M)
    st_ref, met_ref = _run(problem, ref_engine.build_round_step,
                           ref_engine.init_state, spec_ref, H=H, n_clients=M)
    np.testing.assert_array_equal(np.asarray(st_new["params"]["x"]),
                                  np.asarray(st_ref["params"]["x"]))
    np.testing.assert_array_equal(np.asarray(st_new["mom"]["x"]),
                                  np.asarray(st_ref["mom"]["x"]))
    if "server" in st_ref:
        np.testing.assert_array_equal(np.asarray(st_new["server"]["v"]["x"]),
                                      np.asarray(st_ref["server"]["v"]["x"]))
    assert float(met_new["loss"]) == float(met_ref["loss"])
    assert "buffer" not in st_new
    assert "staleness" not in met_new


# --------------------------------------------------------------------------- #
# masked client loop vs per-client Python-loop oracle
# --------------------------------------------------------------------------- #


def _oracle_round(loss, x0, mom0, batch, h_m, lr, momentum, grad_clip=0.0,
                  weight_decay=0.0):
    """Per-client Python loop: client m runs h_m[m] heavy-ball SGD steps on
    its own microbatches, then params (and momentum) are plainly averaged.
    Each step's gradient is clipped to global norm ``grad_clip`` (0 = off),
    then ``weight_decay``·x is added. Also returns how many steps clipped."""
    grad = jax.grad(lambda x, mc: loss({"x": x}, mc))
    xs, ms, final_losses = [], [], []
    n_clipped = 0
    for m in range(len(h_m)):
        x, mo = x0.copy(), mom0[m].copy()
        for h in range(h_m[m]):
            micro = {k: jnp.asarray(v[m, h]) for k, v in batch.items()}
            l = float(loss({"x": jnp.asarray(x)}, micro))
            g = np.asarray(grad(jnp.asarray(x), micro))
            if grad_clip:
                nrm = np.sqrt(np.vdot(g, g) + 1e-12)
                n_clipped += int(nrm > grad_clip)
                g = g * min(1.0, grad_clip / nrm)
            g = g + weight_decay * x
            mo = momentum * mo + g
            x = x - lr * mo
        xs.append(x)
        ms.append(mo)
        final_losses.append(l)
    return (np.mean(xs, axis=0), np.mean(ms, axis=0),
            np.asarray(final_losses), n_clipped)


@pytest.mark.parametrize("momentum,grad_clip,weight_decay", [
    pytest.param(0.0, 0.0, 0.0, id="0.0"),
    pytest.param(0.9, 0.0, 0.0, id="0.9"),
    pytest.param(0.9, 0.3, 0.0, id="clip"),
    pytest.param(0.9, 0.0, 0.05, id="wd"),
    pytest.param(0.9, 0.3, 0.05, id="clip-wd"),
])
def test_masked_loop_matches_python_oracle(problem, momentum, grad_clip,
                                           weight_decay):
    """One heterogeneous round (H_m = 1..H) equals the per-client oracle:
    frozen clients contribute their step-H_m state to the sync average, and
    loss_per_client reports each client's OWN final step — also with the
    per-step global-norm grad clip and weight decay composed in."""
    H, M = 4, 4
    h_m = (1, 2, 4, 3)
    loss = _quad_loss(problem)
    if momentum:   # savic heavy-ball clients, identity D, momentum averaged
        spec = engine.method_spec("savic", **{**MS_KW, "beta1": momentum},
                                  pc_kind="identity", local_steps=h_m)
        spec = dataclasses.replace(spec, client=dataclasses.replace(
            spec.client, grad_clip=grad_clip, weight_decay=weight_decay))
        lr = MS_KW["gamma"]
    else:          # fedavg plain-SGD clients
        spec = engine.method_spec("fedavg", **MS_KW, local_steps=h_m)
        lr = MS_KW["eta_l"]
    step = jax.jit(engine.build_round_step(loss, spec))
    state = engine.init_state(jax.random.PRNGKey(0),
                              lambda k: {"x": jnp.zeros(24)}, spec, M)
    loader = QuadraticLoader(problem, seed=0)
    batch = {k: np.asarray(v) for k, v in loader.round_batch(H).items()}
    new_state, met = step(state, jax.tree.map(jnp.asarray, batch),
                          jax.random.PRNGKey(9))
    x_avg, m_avg, final_losses, n_clipped = _oracle_round(
        loss, np.zeros(24), np.asarray(state["mom"]["x"]), batch, h_m,
        lr, momentum, grad_clip, weight_decay)
    if grad_clip:
        assert n_clipped > 0       # the clip really engaged
    np.testing.assert_allclose(np.asarray(new_state["params"]["x"][0]),
                               x_avg, rtol=1e-6, atol=1e-7)
    if momentum:
        np.testing.assert_allclose(np.asarray(new_state["mom"]["x"][0]),
                                   m_avg, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(met["loss_per_client"]),
                               final_losses, rtol=1e-6)


def test_uniform_truncated_hm_equals_truncated_batch(problem):
    """H_m = (h, h, ..., h) with h < H masks the tail steps: the result is
    bitwise the plain engine run on the batch truncated to h microbatches
    (the masked steps' arithmetic is computed and fully discarded)."""
    H, h, M = 5, 2, 4
    loss = _quad_loss(problem)
    loader = QuadraticLoader(problem, seed=0)
    batch = {k: np.asarray(v) for k, v in loader.round_batch(H).items()}
    spec_m = engine.method_spec("fedavg", **MS_KW, local_steps=(h,) * M)
    spec_u = engine.method_spec("fedavg", **MS_KW)
    init = lambda k: {"x": jnp.zeros(24)}
    st_m = engine.init_state(jax.random.PRNGKey(0), init, spec_m, M)
    st_u = engine.init_state(jax.random.PRNGKey(0), init, spec_u, M)
    key = jax.random.PRNGKey(7)
    out_m, _ = jax.jit(engine.build_round_step(loss, spec_m))(
        st_m, jax.tree.map(jnp.asarray, batch), key)
    trunc = {k: jnp.asarray(v[:, :h]) for k, v in batch.items()}
    out_u, _ = jax.jit(engine.build_round_step(loss, spec_u))(
        st_u, trunc, key)
    np.testing.assert_array_equal(np.asarray(out_m["params"]["x"]),
                                  np.asarray(out_u["params"]["x"]))


@pytest.mark.parametrize("method,kw", [
    ("local-adam", {}),
    ("savic", dict(pc_kind="oasis", scaling="local")),   # Hutchinson stat
], ids=["local-adam", "oasis-local"])
def test_local_scaling_masks_per_client_preconditioner(problem, method, kw):
    """Local scaling with heterogeneous H_m: a frozen client's per-client D
    and step counter t freeze too (the D of client m reflects h_m[m]
    updates), for an Adam-family stat and for a Hutchinson one."""
    H, M = 4, 4
    h_m = (1, 4, 2, 3)
    spec = engine.method_spec(method, **MS_KW, **kw, local_steps=h_m)
    state, met = _run(problem, engine.build_round_step, engine.init_state,
                      spec, rounds=1, H=H, n_clients=M)
    t = np.asarray(state["precond"]["t"])
    np.testing.assert_array_equal(t, np.asarray(h_m))
    assert np.isfinite(float(met["loss"]))


# --------------------------------------------------------------------------- #
# staleness weights + the buffered server vs a Python FIFO oracle
# --------------------------------------------------------------------------- #


def test_staleness_weights_normalize_and_reduce():
    """w sums to 1 for every (B, weighting, round); invalid (not-yet-
    populated) slots get weight 0; B=1 is plain delta averaging (w = [1])."""
    for B in (1, 2, 3, 6):
        for wt in engine.STALENESS_WEIGHTINGS:
            for r in (0, 1, B - 1, B + 3, 100):
                w = np.asarray(engine.staleness_weights(
                    engine.AsyncSpec(buffer_rounds=B, weighting=wt),
                    jnp.int32(r)))
                np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-6)
                assert (w >= 0).all()
                assert (w[min(r, B - 1) + 1:] == 0).all()
    np.testing.assert_array_equal(
        np.asarray(engine.staleness_weights(
            engine.AsyncSpec(buffer_rounds=1), jnp.int32(0))), [1.0])
    # polynomial weighting decays with staleness over the populated prefix
    w = np.asarray(engine.staleness_weights(
        engine.AsyncSpec(buffer_rounds=4, weighting="polynomial"),
        jnp.int32(10)))
    assert (np.diff(w) < 0).all()


def test_async_b1_reduces_to_plain_averaging(problem):
    """A depth-1 buffer holds only the fresh delta (staleness 0): the
    trajectory matches the synchronous engine to fp32 tolerance (the delta
    round-trip x_t + (x̄ − x_t) is not a bitwise identity — that is why the
    identity short-circuit is B = 0, not B = 1)."""
    spec_b = engine.method_spec("fedavg", **MS_KW, async_buffer=1)
    spec_s = engine.method_spec("fedavg", **MS_KW)
    st_b, met_b = _run(problem, engine.build_round_step, engine.init_state,
                       spec_b)
    st_s, _ = _run(problem, engine.build_round_step, engine.init_state,
                   spec_s)
    assert st_b["buffer"]["x"].shape == (1, 24)
    np.testing.assert_allclose(np.asarray(st_b["params"]["x"]),
                               np.asarray(st_s["params"]["x"]),
                               rtol=1e-5, atol=1e-7)
    assert float(met_b["staleness"]) == 0.0


def _oracle_buffered(loss, batch_rounds, keys, h_m, lr, B, weighting,
                     poly_a=0.5, M=4, d=24):
    """Python FIFO oracle for the staleness-buffered averaging server,
    composed with heterogeneous H_m masking."""
    grad = jax.grad(lambda x, mc: loss({"x": x}, mc))
    x = np.zeros(d)
    buf = [np.zeros(d) for _ in range(B)]
    for t, batch in enumerate(batch_rounds):
        xs = []
        for m in range(M):
            xm = x.copy()
            for h in range(h_m[m]):
                micro = {k: jnp.asarray(v[m, h]) for k, v in batch.items()}
                xm = xm - lr * np.asarray(grad(jnp.asarray(xm), micro))
            xs.append(xm)
        delta = np.mean(xs, axis=0) - x
        buf = [delta] + buf[:-1]
        ages = np.arange(B, dtype=np.float64)
        s = np.ones(B) if weighting == "constant" else (1 + ages) ** -poly_a
        w = s * (ages <= t)
        w = w / w.sum()
        x = x + sum(wi * bi for wi, bi in zip(w, buf))
    return x


@pytest.mark.parametrize("weighting", ["constant", "polynomial"])
def test_async_buffer_matches_python_oracle(problem, weighting):
    """The buffered engine (composed with heterogeneous H_m) equals the
    Python FIFO oracle over multiple rounds, including the early rounds where
    the weights renormalize over the populated prefix."""
    H, M, B, rounds = 3, 4, 3, 6
    h_m = (1, 3, 2, 3)
    loss = _quad_loss(problem)
    spec = engine.method_spec(
        "fedavg", **MS_KW, local_steps=h_m,
        asynchrony=engine.AsyncSpec(buffer_rounds=B, weighting=weighting))
    step = jax.jit(engine.build_round_step(loss, spec))
    state = engine.init_state(jax.random.PRNGKey(0),
                              lambda k: {"x": jnp.zeros(24)}, spec, M)
    loader = QuadraticLoader(problem, seed=0)
    key = jax.random.PRNGKey(1)
    batches, keys = [], []
    for _ in range(rounds):
        key, k = jax.random.split(key)
        batches.append({k_: np.asarray(v)
                        for k_, v in loader.round_batch(H).items()})
        keys.append(k)
        state, met = step(state, jax.tree.map(jnp.asarray, batches[-1]),
                          keys[-1])
    x_oracle = _oracle_buffered(loss, batches, keys, h_m, MS_KW["eta_l"], B,
                                weighting)
    np.testing.assert_allclose(np.asarray(state["params"]["x"][0]), x_oracle,
                               rtol=1e-5, atol=1e-6)
    # the applied staleness E_w[τ] is positive once the buffer is populated
    assert float(met["staleness"]) > 0.0


def test_async_buffer_with_adaptive_server_runs(problem):
    """The buffer composes with the adaptive server (the staleness-weighted
    delta is the pseudo-gradient) and with compression."""
    spec = engine.method_spec(
        "fedadam", **MS_KW, async_buffer=2,
        compression=engine.CompressionSpec(op="topk", k=0.5,
                                           error_feedback=True))
    state, met = _run(problem, engine.build_round_step, engine.init_state,
                      spec, rounds=5)
    assert "buffer" in state and "ef" in state and "server" in state
    assert state["buffer"]["x"].shape == (2, 24)
    assert np.isfinite(float(met["loss"]))
    assert np.isfinite(float(met["step_norm"]))


# --------------------------------------------------------------------------- #
# systems-heterogeneity models (data/federated.py)
# --------------------------------------------------------------------------- #


def test_sample_step_times_models():
    t = fed.sample_step_times("uniform", 8)
    np.testing.assert_array_equal(t, np.ones(8))
    t = fed.sample_step_times("lognormal", 64, seed=1, sigma=0.8)
    assert t.min() == 1.0 and t.max() > 1.0 and t.shape == (64,)
    np.testing.assert_array_equal(
        t, fed.sample_step_times("lognormal", 64, seed=1, sigma=0.8))
    t2 = fed.sample_step_times("tiers", 64, seed=2, tiers=(1.0, 2.0, 4.0))
    assert set(np.unique(t2)).issubset({1.0, 2.0, 4.0})
    with pytest.raises(ValueError):
        fed.sample_step_times("gaussian", 4)


def test_local_steps_budget():
    """Fixed wall-clock budget: the fastest client runs all H steps, a 2×
    slower client about H/2, everyone at least 1."""
    times = np.array([1.0, 2.0, 4.0, 100.0])
    h = fed.local_steps_from_times(times, 8)
    np.testing.assert_array_equal(h, [8, 4, 2, 1])
    h = fed.sample_local_steps("lognormal", 32, 8, seed=3)
    assert h.min() >= 1 and h.max() == 8
    np.testing.assert_array_equal(h, fed.sample_local_steps(
        "lognormal", 32, 8, seed=3))


def test_simulated_round_time():
    times = np.array([1.0, 3.0])
    assert fed.simulated_round_time(times, [4, 4]) == 12.0
    assert fed.simulated_round_time(times, [4, 2], barrier="sync") == 6.0
    assert fed.simulated_round_time(times, [4, 2], barrier="async",
                                    buffer_rounds=3) == 2.0
    with pytest.raises(ValueError):
        fed.simulated_round_time(times, [1, 1], barrier="maybe")


# --------------------------------------------------------------------------- #
# spec validation + trace-time shape errors
# --------------------------------------------------------------------------- #


def test_spec_validation():
    with pytest.raises(ValueError):
        engine.AsyncSpec(buffer_rounds=-1)
    with pytest.raises(ValueError):
        engine.AsyncSpec(weighting="exponential")
    with pytest.raises(ValueError):
        engine.AsyncSpec(poly_a=0.0)
    with pytest.raises(ValueError):
        engine.ClientLoopSpec(local_steps=(2, 0, 1))
    with pytest.raises(ValueError):
        engine.ClientLoopSpec(local_steps=())
    with pytest.raises(ValueError):
        engine.SyncSpec(asynchrony="fedbuff")  # must be an AsyncSpec
    # valid settings still construct, and normalize to hashable tuples
    s = engine.ClientLoopSpec(local_steps=np.array([2, 3], np.int64))
    assert s.local_steps == (2, 3)
    hash(engine.method_spec("fedavg", local_steps=(1, 2), async_buffer=2))


def test_trace_time_shape_errors(problem):
    loss = _quad_loss(problem)
    loader = QuadraticLoader(problem, seed=0)
    batch = jax.tree.map(jnp.asarray, loader.round_batch(3))
    init = lambda k: {"x": jnp.zeros(24)}
    # wrong M
    spec = engine.method_spec("fedavg", **MS_KW, local_steps=(1, 2))
    state = engine.init_state(jax.random.PRNGKey(0), init, spec, 4)
    with pytest.raises(ValueError, match="entries for"):
        engine.build_round_step(loss, spec)(state, batch, jax.random.PRNGKey(0))
    # H_m beyond the round's H microbatches
    spec = engine.method_spec("fedavg", **MS_KW, local_steps=(3, 3, 3, 9))
    state = engine.init_state(jax.random.PRNGKey(0), init, spec, 4)
    with pytest.raises(ValueError, match="exceeds"):
        engine.build_round_step(loss, spec)(state, batch, jax.random.PRNGKey(0))


# --------------------------------------------------------------------------- #
# property-style invariants (hypothesis via the compat shim)
# --------------------------------------------------------------------------- #


@given(st.integers(min_value=1, max_value=8),
       st.integers(min_value=0, max_value=50),
       st.sampled_from(engine.STALENESS_WEIGHTINGS),
       st.floats(min_value=0.1, max_value=3.0))
@settings(max_examples=40, deadline=None)
def test_staleness_weights_property(B, r, weighting, poly_a):
    w = np.asarray(engine.staleness_weights(
        engine.AsyncSpec(buffer_rounds=B, weighting=weighting,
                         poly_a=poly_a), jnp.int32(r)))
    np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-6)
    assert (w >= 0).all() and w.shape == (B,)


@given(st.integers(min_value=1, max_value=400),
       st.integers(min_value=2, max_value=16))
@settings(max_examples=20, deadline=None)
def test_local_steps_bounds_property(seed, h_max):
    h = fed.sample_local_steps("lognormal", 16, h_max, seed=seed)
    assert h.shape == (16,) and h.min() >= 1 and h.max() <= h_max
    assert h.max() == h_max  # the fastest client always runs the full budget


# --------------------------------------------------------------------------- #
# launch layer: H_m threading + buffer sharding through build_train_step
# --------------------------------------------------------------------------- #


def test_build_train_step_threads_het_and_buffer_sharding():
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.configs import ShapeConfig
    from repro.launch.steps import build_train_step

    dev = np.array(jax.devices("cpu")[:1]).reshape(1, 1)
    mesh = Mesh(dev, ("data", "model"))
    shape = ShapeConfig("tiny_train", 32, 2, "train")
    asy = engine.AsyncSpec(buffer_rounds=3, weighting="polynomial")
    built = build_train_step("qwen2-0.5b", shape, mesh, method="fedadam",
                             reduced=True, h_local=2, het_model="lognormal",
                             asynchrony=asy)
    spec = built.meta["engine_spec"]
    assert spec.sync.asynchrony == asy
    assert spec.client.local_steps is not None
    assert built.meta["het_model"] == "lognormal"
    assert built.meta["sim_round_time_sync"] > 0
    # the "async" pace is only recorded when a buffer actually exists (B>0);
    # pure H_m budgeting is labeled sim_round_time_budgeted instead
    assert built.meta["sim_round_time_async"] <= \
        built.meta["sim_round_time_budgeted"]
    state_shape = built.args[0]
    assert "buffer" in state_shape
    b0 = jax.tree.leaves(state_shape["buffer"])[0]
    assert b0.shape[0] == 3                      # leading B dim
    state_spec, _ = built.in_shardings
    assert jax.tree.structure(state_spec["buffer"]) \
        == jax.tree.structure(state_shape["buffer"])
    for s in jax.tree.leaves(state_spec["buffer"]):
        assert s.spec[0] is None                 # B never sharded
