"""Round-engine tests: regression against the pre-refactor monoliths,
SyncStrategy coverage (participation / quantized sync), adaptive-server
methods end-to-end, and the build_train_step method selector."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _reference_fedopt as ref_fedopt
import _reference_savic as ref_savic
from repro.core import engine, fedopt, savic
from repro.core.preconditioner import PrecondConfig
from repro.core.savic import SavicConfig
from repro.data import QuadraticLoader, QuadraticProblem


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


def _trajectories(problem, step_a, state_a, step_b, state_b, rounds=6, H=5,
                  seed=0):
    """Run two round implementations on identical fixed-seed batches; return
    the per-round (params_a, params_b) pairs."""
    loader = QuadraticLoader(problem, seed=seed)
    key = jax.random.PRNGKey(seed + 1)
    out = []
    for _ in range(rounds):
        key, k = jax.random.split(key)
        batch = jax.tree.map(jnp.asarray, loader.round_batch(H))
        state_a, met_a = step_a(state_a, batch, k)
        state_b, met_b = step_b(state_b, batch, k)
        out.append((state_a, state_b, met_a, met_b))
    return out


# --------------------------------------------------------------------------- #
# regression: engine-based SAVIC == pre-refactor monolith (fixed seed)
# --------------------------------------------------------------------------- #


SAVIC_REGRESSION_CASES = {
    "adam-global-momentum": (
        PrecondConfig(kind="adam", alpha=1e-2),
        dict(gamma=0.03, beta1=0.9)),
    "oasis-local": (
        PrecondConfig(kind="oasis", alpha=1e-2),
        dict(gamma=0.03, beta1=0.5, scaling="local")),
    "rmsprop-avg-local-stat": (
        PrecondConfig(kind="rmsprop", alpha=1e-2),
        dict(gamma=0.03, beta1=0.0, stat_source="avg_local")),
    "identity-participation-bf16": (
        PrecondConfig(kind="identity"),
        dict(gamma=0.03, beta1=0.0, participation=0.5,
             sync_dtype="bfloat16")),
}
# the scaled local step's whole matrix: every PrecondConfig kind × β_t
# schedule × rule-4 clip × scaling mode. adahessian × debias × local feeds a
# negative v⊙Hv stat into the √d magnitude and goes NaN in both programs;
# those cases pin equal NaN positions.
SAVIC_REGRESSION_CASES.update({
    f"{kind}-{schedule}-{clip}-{scaling}": (
        PrecondConfig(kind=kind, alpha=1e-2, beta_schedule=schedule,
                      clip=clip),
        dict(gamma=0.01, beta1=0.9, scaling=scaling))
    for kind, schedule, clip, scaling in itertools.product(
        ("adam", "rmsprop", "adagrad", "oasis", "adahessian"),
        ("const", "debias"), ("max", "add"), ("global", "local"))})
# grad clip and weight decay composed with the local step
SAVIC_REGRESSION_CASES.update({
    f"adam-local-{name}": (
        PrecondConfig(kind="adam", alpha=1e-2),
        dict(gamma=0.01, beta1=0.9, scaling="local", **extra))
    for name, extra in (("clip", dict(grad_clip=0.5)),
                        ("wd", dict(weight_decay=0.01)),
                        ("clip-wd", dict(grad_clip=0.3, weight_decay=0.05)))})


@pytest.mark.parametrize("case", list(SAVIC_REGRESSION_CASES))
def test_savic_engine_matches_prerefactor(problem, case):
    """The engine emits the same program the monolithic savic.py did:
    trajectories agree bitwise (NaN positions included) for every layer
    combination — preconditioner kind, β_t schedule, clip, scaling mode,
    momentum, stat source, participation, quantized sync, grad clip and
    weight decay."""
    pc, sv_kw = SAVIC_REGRESSION_CASES[case]
    loss = _quad_loss(problem)
    sv_new = SavicConfig(**sv_kw)
    sv_old = ref_savic.SavicConfig(**sv_kw)
    step_new = jax.jit(savic.build_round_step(loss, pc, sv_new))
    step_old = jax.jit(ref_savic.build_round_step(loss, pc, sv_old))
    init = lambda k: {"x": jnp.zeros(problem.b.shape[1])}
    st_new = savic.init_state(jax.random.PRNGKey(0), init, pc, sv_new, 4)
    st_old = ref_savic.init_state(jax.random.PRNGKey(0), init, pc, sv_old, 4)
    for st_n, st_o, met_n, met_o in _trajectories(problem, step_new, st_new,
                                                  step_old, st_old):
        for name in ("params", "mom", "precond"):
            jax.tree.map(lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b), err_msg=name),
                st_n[name], st_o[name])
        for name in ("loss", "client_drift"):
            np.testing.assert_array_equal(np.asarray(met_n[name]),
                                          np.asarray(met_o[name]),
                                          err_msg=name)


@pytest.mark.parametrize("server_opt", ["adagrad", "adam", "yogi"])
def test_fedopt_engine_matches_prerefactor(problem, server_opt):
    """Engine-based FedOpt reproduces the pre-refactor trajectories to fp32
    tolerance (the engine averages post-step params then subtracts x_t, the
    monolith averaged per-client deltas — identical up to float summation
    order)."""
    loss = _quad_loss(problem)
    kw = dict(server_opt=server_opt, eta=0.1, eta_l=0.02, tau=1e-2)
    cfg_new = fedopt.FedOptConfig(**kw)
    cfg_old = ref_fedopt.FedOptConfig(**kw)
    step_new = jax.jit(fedopt.build_round_step(loss, cfg_new))
    step_old = jax.jit(ref_fedopt.build_round_step(loss, cfg_old))
    init = lambda k: {"x": jnp.zeros(problem.b.shape[1])}
    st_new = fedopt.init_state(jax.random.PRNGKey(0), init, cfg_new)
    st_old = ref_fedopt.init_state(jax.random.PRNGKey(0), init, cfg_old)
    for st_n, st_o, met_n, met_o in _trajectories(problem, step_new, st_new,
                                                  step_old, st_old):
        np.testing.assert_allclose(np.asarray(st_n["params"]["x"]),
                                   np.asarray(st_o["params"]["x"]),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(st_n["v"]["x"]),
                                   np.asarray(st_o["v"]["x"]),
                                   rtol=1e-5, atol=1e-8)
        np.testing.assert_allclose(float(met_n["step_norm"]),
                                   float(met_o["step_norm"]), rtol=1e-4)


# --------------------------------------------------------------------------- #
# SyncStrategy: participation weights + quantized sync error bound
# --------------------------------------------------------------------------- #


def test_participation_weights_sum_to_one():
    key = jax.random.PRNGKey(0)
    for M, part in [(4, 0.5), (8, 0.25), (8, 1.0), (5, 0.3), (3, 0.01)]:
        w = np.asarray(engine.participation_weights(
            engine.SyncSpec(participation=part), key, M))
        np.testing.assert_allclose(w.sum(), 1.0, rtol=1e-6)
        n_part = max(1, int(round(part * M)))
        assert (w > 0).sum() == n_part
        np.testing.assert_allclose(w[w > 0], 1.0 / n_part, rtol=1e-6)


def test_partial_participation_only_sampled_clients_enter_mean():
    """With participation<1 the sync average is the plain mean of exactly the
    sampled subset — non-participants contribute nothing."""
    M, d = 8, 16
    key = jax.random.PRNGKey(7)
    spec = engine.SyncSpec(participation=0.5)
    w = np.asarray(engine.participation_weights(spec, key, M))
    avg = engine.make_sync(spec, key, M)
    vals = jnp.asarray(np.random.default_rng(0).normal(size=(M, d)),
                       jnp.float32)
    got = np.asarray(avg(vals))
    sampled = np.where(w > 0)[0]
    assert len(sampled) == 4
    np.testing.assert_allclose(got, np.asarray(vals)[sampled].mean(axis=0),
                               rtol=1e-5, atol=1e-6)
    # and the weighted mean ignores non-participants entirely
    vals_poisoned = np.asarray(vals).copy()
    vals_poisoned[[m for m in range(M) if m not in set(sampled)]] = 1e9
    got_p = np.asarray(avg(jnp.asarray(vals_poisoned)))
    np.testing.assert_allclose(got_p, got, rtol=1e-6)


def test_sync_dtype_quantization_error_bounded():
    """bf16 sync average stays within the representation's relative error of
    the full-precision average (~2^-8 per element; bound used: 2^-7 on the
    value scale)."""
    M, d = 8, 256
    key = jax.random.PRNGKey(3)
    rng = np.random.default_rng(1)
    vals = jnp.asarray(rng.normal(size=(M, d)).astype(np.float32) * 3.0)
    full = np.asarray(engine.make_sync(engine.SyncSpec(), key, M)(vals))
    quant = np.asarray(engine.make_sync(
        engine.SyncSpec(sync_dtype="bfloat16"), key, M)(vals),
        dtype=np.float32)
    scale = np.abs(np.asarray(vals)).max()
    err = np.abs(quant - full).max()
    assert err <= scale * 2.0 ** -7, (err, scale)
    assert err > 0.0   # it really is quantized, not a no-op


# --------------------------------------------------------------------------- #
# adaptive-server methods end-to-end through the engine
# --------------------------------------------------------------------------- #


def _run_method(problem, spec, rounds=40, H=5, seed=0):
    loss = _quad_loss(problem)
    step = jax.jit(engine.build_round_step(loss, spec))
    M, d = problem.b.shape
    state = engine.init_state(jax.random.PRNGKey(seed),
                              lambda k: {"x": jnp.zeros(d)}, spec, M)
    loader = QuadraticLoader(problem, seed=seed)
    key = jax.random.PRNGKey(seed + 1)
    mets = []
    for _ in range(rounds):
        key, k = jax.random.split(key)
        batch = jax.tree.map(jnp.asarray, loader.round_batch(H))
        state, met = step(state, batch, k)
        mets.append({k2: float(v) for k2, v in met.items()
                     if np.ndim(v) == 0})
    return state, mets


def test_fedadam_preset_converges(problem):
    spec = engine.method_spec("fedadam", eta=0.1, eta_l=0.02, tau=1e-2)
    state, mets = _run_method(problem, spec)
    assert "server" in state and "m" in state["server"]
    assert mets[-1]["loss"] < mets[0]["loss"]
    assert all("step_norm" in m for m in mets)


def test_local_adam_composed_scenario_converges(problem):
    """The new composed method (cf. 2409.13155): per-client Adam scaling
    updated every local step AND an adaptive Adam server on Δ."""
    spec = engine.method_spec("local-adam", pc_kind="adam", alpha=1e-2,
                              eta=0.05, eta_l=0.01, tau=1e-2)
    assert spec.client.scaling == "local"
    assert spec.server.kind == "adaptive"
    state, mets = _run_method(problem, spec, rounds=50)
    # local scaling state carries the client dim; server m/v do not
    assert state["precond"]["d"]["x"].shape == (4, 24)
    assert state["server"]["m"]["x"].shape == (24,)
    assert mets[-1]["loss"] < mets[0]["loss"]


def test_every_method_spec_resolves_and_steps(problem):
    """One round of every preset runs and returns finite metrics."""
    loss = _quad_loss(problem)
    loader = QuadraticLoader(problem, seed=0)
    for method in engine.METHODS:
        spec = engine.method_spec(method, gamma=0.01, alpha=1e-2,
                                  eta_l=0.01, eta=0.05)
        step = jax.jit(engine.build_round_step(loss, spec))
        state = engine.init_state(jax.random.PRNGKey(0),
                                  lambda k: {"x": jnp.zeros(24)}, spec, 4)
        batch = jax.tree.map(jnp.asarray, loader.round_batch(3))
        state, met = step(state, batch, jax.random.PRNGKey(1))
        assert np.isfinite(float(met["loss"])), method
        assert int(state["round"]) == 1, method


# --------------------------------------------------------------------------- #
# launch layer: build_train_step method selector + sharding-spec derivation
# --------------------------------------------------------------------------- #


def _tiny_mesh():
    from jax.sharding import Mesh
    dev = np.array(jax.devices("cpu")[:1]).reshape(1, 1)
    return Mesh(dev, ("data", "model"))


@pytest.mark.parametrize("method", ["savic", "fedadam", "local-adam"])
def test_build_train_step_method_selector(method):
    from repro.configs import ShapeConfig
    from repro.launch.steps import build_train_step

    shape = ShapeConfig("tiny_train", 32, 2, "train")
    built = build_train_step("qwen2-0.5b", shape, _tiny_mesh(), method=method,
                             reduced=True, h_local=2)
    assert built.meta["method"] == method
    state_shape = built.args[0]
    state_spec, _ = built.in_shardings
    if method == "savic":
        assert "server" not in state_shape
    else:
        # adaptive server: m/v shaped like ONE replica, specs derived
        p0 = jax.tree.leaves(state_shape["params"])[0]
        m0 = jax.tree.leaves(state_shape["server"]["m"])[0]
        assert m0.shape == p0.shape[1:]
        assert jax.tree.structure(state_spec["server"]["m"]) \
            == jax.tree.structure(state_shape["server"]["m"])
    if method == "local-adam":
        # per-client D: leading client dim on both d and t
        p0 = jax.tree.leaves(state_shape["params"])[0]
        d0 = jax.tree.leaves(state_shape["precond"]["d"])[0]
        assert d0.shape[0] == p0.shape[0]
        assert state_shape["precond"]["t"].shape == (p0.shape[0],)


def test_build_train_step_fedadam_executes():
    """Acceptance: build_train_step(..., method='fedadam') runs end-to-end —
    compile with the derived shardings and take one real round step."""
    from repro.configs import ShapeConfig
    from repro.launch.steps import build_train_step

    mesh = _tiny_mesh()
    shape = ShapeConfig("tiny_train", 32, 2, "train")
    built = build_train_step("qwen2-0.5b", shape, mesh, method="fedadam",
                             reduced=True, h_local=2)
    with mesh:
        fn = jax.jit(built.fn, in_shardings=built.in_shardings,
                     out_shardings=built.out_shardings)
        key = jax.random.PRNGKey(0)
        spec = engine.method_spec("fedadam")
        from repro.configs import get_config
        from repro.models import ModelCallConfig, build as build_model
        model = build_model(get_config("qwen2-0.5b", reduced=True),
                            ModelCallConfig())
        state = engine.init_state(key, model.init, spec, 1)
        batch = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype) if s.dtype != jnp.int32
            else jnp.ones(s.shape, jnp.int32), built.args[1])
        new_state, metrics = fn(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        assert float(new_state["round"]) == 1
