"""Client objectives & personalization — the differential test harness.

Locks down DESIGN.md §12:
  * differential pinning: the supervised / no-personalization configuration
    is bit-identical to the pre-PR engine snapshot
    (tests/_reference_engine.py) for all six METHODS — both objective=None
    and an explicit identity ClientObjective;
  * objective math: masked CE 0/0-safety, consistency at σ=0 and
    pseudo-label at an unreachable threshold both collapse to the
    supervised term, the unlabeled term engages when gated open;
  * personalization never crosses the wire: a poison value planted in a
    personal leaf stays per-client forever, server state carries no
    personal leaves, ``bytes_on_wire`` accounting drops exactly the
    personal subset, and checkpoints round-trip the stripped state;
  * the fused Pallas client loop stays engaged (bit-equal to the tree
    path) under a non-identity objective;
  * loader plumbing: the ``labeled`` leaf appears only when requested and
    is round-addressable;
  * launch threading: build_train_step records the objective meta, aligns
    the stripped sharding specs, and rejects personal × global-D builds.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _reference_engine as ref_engine
from repro.core import engine, objectives
from repro.data import (ClassificationData, FederatedLoader, LMRoundLoader,
                        QuadraticLoader, QuadraticProblem, TokenStream,
                        labeled_mask, main_class_partition)


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
         n_clients=4, objective=None, init_fn=None):
    loss = _quad_loss(problem)
    kw = {} if objective is None and build_round_step \
        is ref_engine.build_round_step else {"objective": objective}
    step = jax.jit(build_round_step(loss, spec, **kw)
                   if kw else build_round_step(loss, spec))
    init_fn = init_fn or (lambda k: {"x": jnp.zeros(24)})
    state = init_state(jax.random.PRNGKey(0), init_fn, spec, n_clients)
    loader = QuadraticLoader(problem, seed=seed)
    key = jax.random.PRNGKey(seed + 1)
    for _ in range(rounds):
        key, k = jax.random.split(key)
        state, met = step(state, jax.tree.map(jnp.asarray,
                                              loader.round_batch(H)), k)
    return state, met


MS_KW = dict(gamma=0.01, alpha=1e-2, eta_l=0.01, eta=0.05)


# --------------------------------------------------------------------------- #
# differential: supervised / no-personalization == pre-PR engine, bitwise
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("method", engine.METHODS)
def test_supervised_bit_identical_to_prepr_engine(problem, method):
    """objective=None + personal=() emits the exact pre-objectives program:
    trajectories agree BITWISE with the verbatim engine snapshot."""
    spec_new = engine.method_spec(method, **MS_KW)
    assert spec_new.sync.personal == ()
    spec_ref = ref_engine.method_spec(method, **MS_KW)
    st_new, met_new = _run(problem, engine.build_round_step,
                           engine.init_state, spec_new)
    st_ref, met_ref = _run(problem, ref_engine.build_round_step,
                           ref_engine.init_state, spec_ref)
    np.testing.assert_array_equal(np.asarray(st_new["params"]["x"]),
                                  np.asarray(st_ref["params"]["x"]))
    np.testing.assert_array_equal(np.asarray(st_new["mom"]["x"]),
                                  np.asarray(st_ref["mom"]["x"]))
    if "server" in st_ref:
        np.testing.assert_array_equal(np.asarray(st_new["server"]["v"]["x"]),
                                      np.asarray(st_ref["server"]["v"]["x"]))
    assert float(met_new["loss"]) == float(met_ref["loss"])


def _quad_objective(problem, kind="consistency", noise=0.0):
    """A ClientObjective over quadratic micros (loss gets an optional keyed
    perturbation so the trajectory provably consumes the objective key)."""
    base = _quad_loss(problem)

    def loss(params, micro, key):
        eps = noise * jax.random.normal(key, ()) if noise else 0.0
        return base(params, micro) * (1.0 + eps)

    return objectives.ClientObjective(
        spec=objectives.ObjectiveSpec(kind=kind), loss=loss, base_loss=base)


def test_identity_objective_bit_identical(problem):
    """An explicit supervised ClientObjective short-circuits to the unkeyed
    grad path — bitwise equal to objective=None."""
    spec = engine.method_spec("savic", **MS_KW)
    ident = objectives.ClientObjective(
        spec=objectives.ObjectiveSpec(kind="supervised"),
        loss=lambda p, mc, k: _quad_loss(problem)(p, mc),
        base_loss=_quad_loss(problem))
    st_a, _ = _run(problem, engine.build_round_step, engine.init_state, spec)
    st_b, _ = _run(problem, engine.build_round_step, engine.init_state, spec,
                   objective=ident)
    np.testing.assert_array_equal(np.asarray(st_a["params"]["x"]),
                                  np.asarray(st_b["params"]["x"]))


def test_nonidentity_objective_changes_trajectory(problem):
    spec = engine.method_spec("savic", **MS_KW)
    st_a, _ = _run(problem, engine.build_round_step, engine.init_state, spec)
    st_b, _ = _run(problem, engine.build_round_step, engine.init_state, spec,
                   objective=_quad_objective(problem, noise=0.3))
    assert not np.array_equal(np.asarray(st_a["params"]["x"]),
                              np.asarray(st_b["params"]["x"]))


# --------------------------------------------------------------------------- #
# objective math
# --------------------------------------------------------------------------- #


def _toy_logits_fn():
    def logits_fn(params, x):
        return x @ params["w"]
    return logits_fn


def _toy_micro(key, b=8, d=4, c=3, labeled=None):
    kx, ky = jax.random.split(key)
    micro = {"x": jax.random.normal(kx, (b, d)),
             "y": jax.random.randint(ky, (b,), 0, c)}
    if labeled is not None:
        micro["labeled"] = jnp.asarray(labeled, jnp.float32)
    return micro


def test_masked_ce_empty_mask_is_zero():
    logits = jnp.array([[2.0, -1.0], [0.5, 0.5]])
    y = jnp.array([0, 1])
    assert float(objectives._masked_ce(logits, y, jnp.zeros(2))) == 0.0
    full = objectives._masked_ce(logits, y, jnp.ones(2))
    assert np.isfinite(float(full)) and float(full) > 0.0


def test_consistency_sigma_zero_collapses_to_supervised():
    """σ=0 makes the perturbed view the clean view — the unlabeled term
    vanishes and only the labeled-subset CE remains."""
    spec = objectives.ObjectiveSpec(kind="consistency", noise_sigma=0.0,
                                    unlabeled_weight=5.0)
    obj = objectives.classification_objective(spec, _toy_logits_fn())
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 3))}
    lab = [1, 1, 0, 0, 1, 0, 0, 0]
    micro = _toy_micro(jax.random.PRNGKey(1), labeled=lab)
    got = obj.loss(params, micro, jax.random.PRNGKey(2))
    want = objectives._masked_ce(_toy_logits_fn()(params, micro["x"]),
                                 micro["y"], micro["labeled"])
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)


def test_consistency_noise_engages_unlabeled_term():
    spec = objectives.ObjectiveSpec(kind="consistency", noise_sigma=0.5,
                                    unlabeled_weight=5.0)
    obj = objectives.classification_objective(spec, _toy_logits_fn())
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 3))}
    micro = _toy_micro(jax.random.PRNGKey(1), labeled=[1, 1, 0, 0, 1, 0, 0, 0])
    got = float(obj.loss(params, micro, jax.random.PRNGKey(2)))
    sup = float(objectives._masked_ce(_toy_logits_fn()(params, micro["x"]),
                                      micro["y"], micro["labeled"]))
    assert got > sup


def test_pseudo_label_gate():
    """An unreachable confidence threshold gates the unlabeled term shut
    (loss == supervised); a near-zero one opens it on unlabeled examples."""
    fn = _toy_logits_fn()
    params = {"w": 3.0 * jax.random.normal(jax.random.PRNGKey(0), (4, 3))}
    micro = _toy_micro(jax.random.PRNGKey(1), labeled=[1, 0, 0, 0, 1, 0, 0, 0])
    sup = float(objectives._masked_ce(fn(params, micro["x"]), micro["y"],
                                      micro["labeled"]))
    closed = objectives.classification_objective(
        objectives.ObjectiveSpec(kind="pseudo-label", pseudo_threshold=1 - 1e-9,
                                 unlabeled_weight=2.0), fn)
    np.testing.assert_allclose(
        float(closed.loss(params, micro, jax.random.PRNGKey(2))), sup,
        rtol=1e-6)
    open_ = objectives.classification_objective(
        objectives.ObjectiveSpec(kind="pseudo-label", pseudo_threshold=1e-9,
                                 unlabeled_weight=2.0), fn)
    assert float(open_.loss(params, micro, jax.random.PRNGKey(2))) > sup


def test_missing_labeled_leaf_means_fully_labeled():
    """No 'labeled' leaf -> all-ones mask: a pseudo-label objective on a
    fully labeled batch has an empty gate, so loss == plain CE."""
    fn = _toy_logits_fn()
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (4, 3))}
    micro = _toy_micro(jax.random.PRNGKey(1))
    obj = objectives.classification_objective(
        objectives.ObjectiveSpec(kind="pseudo-label", unlabeled_weight=3.0),
        fn)
    got = float(obj.loss(params, micro, jax.random.PRNGKey(2)))
    want = float(objectives._masked_ce(fn(params, micro["x"]), micro["y"],
                                       jnp.ones(8)))
    np.testing.assert_allclose(got, want, rtol=1e-6)


def test_objective_spec_validation():
    with pytest.raises(ValueError):
        objectives.ObjectiveSpec(kind="nope")
    with pytest.raises(ValueError):
        objectives.ObjectiveSpec(unlabeled_weight=-1.0)
    with pytest.raises(ValueError):
        objectives.ObjectiveSpec(pseudo_threshold=1.5)
    with pytest.raises(ValueError):
        objectives.build_objective(
            objectives.ObjectiveSpec(kind="consistency"))
    assert objectives.build_objective(None) is None
    assert objectives.build_objective(objectives.ObjectiveSpec()) is None


# --------------------------------------------------------------------------- #
# strip / merge machinery
# --------------------------------------------------------------------------- #


def test_strip_personal_identity_for_empty_mask():
    tree = {"a": jnp.ones(3), "b": {"head": jnp.zeros(2)}}
    assert engine.strip_personal((), tree) is tree


def test_strip_personal_substring_match_and_merge():
    tree = {"blocks": {"w": jnp.ones(2)}, "head": {"w": jnp.full(2, 7.0)},
            "final_norm": jnp.full(3, 5.0)}
    stripped = engine.strip_personal(("head", "final_norm"), tree)
    assert stripped["head"]["w"] is None and stripped["final_norm"] is None
    np.testing.assert_array_equal(np.asarray(stripped["blocks"]["w"]),
                                  np.ones(2))
    merged = engine._merge_personal(
        stripped, tree, lambda s, f: s * 0.0)
    np.testing.assert_array_equal(np.asarray(merged["blocks"]["w"]),
                                  np.zeros(2))          # synced: merged via fn
    np.testing.assert_array_equal(np.asarray(merged["head"]["w"]),
                                  np.full(2, 7.0))      # personal: untouched
    np.testing.assert_array_equal(np.asarray(merged["final_norm"]),
                                  np.full(3, 5.0))


def test_sync_spec_personal_validation():
    with pytest.raises(ValueError):
        engine.SyncSpec(personal=("ok", ""))
    with pytest.raises(ValueError):
        engine.SyncSpec(personal="head")  # must be a tuple, not a bare string


# --------------------------------------------------------------------------- #
# personalization: personal leaves provably never cross the wire
# --------------------------------------------------------------------------- #


def _two_leaf_init(poison):
    """params {"x": shared, "head": personal}; ``head`` enters the loss with
    zero gradient so any cross-client mixing could only come from sync."""
    def init(key):
        return {"x": jnp.zeros(24), "head": jnp.asarray(poison, jnp.float32)}
    return init


def _two_leaf_loss(problem):
    base = _quad_loss(problem)

    def loss(params, micro):
        # head's contribution is identically zero (g_head = 0): the leaf can
        # only change if the sync path touches it
        return base({"x": params["x"]}, micro) + 0.0 * jnp.sum(params["head"])
    return loss


@pytest.mark.parametrize("method", ["savic", "fedavg", "fedadam",
                                    "local-adam"])
def test_personal_leaf_poison_never_mixes(problem, method):
    """Plant per-client poison in the personal leaf: after rounds of sync it
    must be exactly where each client left it (zero grad => frozen), while
    the synced leaf is identical across clients after every round."""
    kw = dict(MS_KW)
    if method in ("savic", "local-adam"):
        kw["scaling"] = "local"     # global non-identity D is rejected
    spec = engine.method_spec(method, **kw, personal=("head",))
    loss = _two_leaf_loss(problem)
    step = jax.jit(engine.build_round_step(loss, spec))
    state = engine.init_state(jax.random.PRNGKey(0), _two_leaf_init(0.0),
                              spec, 4)
    poison = jnp.arange(4, dtype=jnp.float32) * 100.0 + 1.0
    state["params"]["head"] = poison
    loader = QuadraticLoader(problem, seed=0)
    key = jax.random.PRNGKey(1)
    for _ in range(3):
        key, k = jax.random.split(key)
        state, _ = step(state, jax.tree.map(jnp.asarray,
                                            loader.round_batch(3)), k)
        np.testing.assert_array_equal(np.asarray(state["params"]["head"]),
                                      np.asarray(poison))
        x = np.asarray(state["params"]["x"])
        np.testing.assert_array_equal(x, np.broadcast_to(x[:1], x.shape))
    if "server" in state:
        for leaf_path, _ in jax.tree_util.tree_flatten_with_path(
                state["server"])[0]:
            assert "head" not in "/".join(str(p) for p in leaf_path)


def test_personal_matches_no_personal_on_synced_leaves(problem):
    """With a zero-gradient personal leaf, the SYNCED leaves' trajectory is
    bitwise the single-leaf run's — stripping is exact, not approximate."""
    spec_p = engine.method_spec("fedadam", **MS_KW, personal=("head",))
    spec_0 = engine.method_spec("fedadam", **MS_KW)
    loss2 = _two_leaf_loss(problem)
    loss1 = _quad_loss(problem)

    def run_with(loss, spec, init_fn):
        step = jax.jit(engine.build_round_step(loss, spec))
        state = engine.init_state(jax.random.PRNGKey(0), init_fn, spec, 4)
        loader = QuadraticLoader(problem, seed=0)
        key = jax.random.PRNGKey(1)
        for _ in range(4):
            key, k = jax.random.split(key)
            state, _ = step(state, jax.tree.map(jnp.asarray,
                                                loader.round_batch(3)), k)
        return state

    st_p = run_with(loss2, spec_p, _two_leaf_init(3.0))
    st_0 = run_with(loss1, spec_0, lambda k: {"x": jnp.zeros(24)})
    np.testing.assert_array_equal(np.asarray(st_p["params"]["x"]),
                                  np.asarray(st_0["params"]["x"]))
    np.testing.assert_array_equal(np.asarray(st_p["server"]["v"]["x"]),
                                  np.asarray(st_0["server"]["v"]["x"]))


def test_personal_global_precond_rejected(problem):
    spec = engine.method_spec("savic", **MS_KW, personal=("head",))
    assert spec.client.scaling == "global" \
        and spec.precond.kind != "identity"
    with pytest.raises(ValueError, match="personal"):
        engine.build_round_step(_two_leaf_loss(problem), spec)


def test_bytes_on_wire_drops_exactly_the_personal_subset():
    """Personalization changes the wire accounting by exactly the personal
    leaves' bytes — the synced subset's accounting is untouched."""
    params = {"x": jax.ShapeDtypeStruct((64,), jnp.float32),
              "head": jax.ShapeDtypeStruct((16,), jnp.float32)}
    spec_p = engine.method_spec("fedadam", personal=("head",))
    spec_0 = engine.method_spec("fedadam")
    w_p = engine.bytes_on_wire(spec_p, params)
    w_0 = engine.bytes_on_wire(spec_0, params)
    w_synced_only = engine.bytes_on_wire(spec_0, {"x": params["x"]})
    assert w_p["total_bytes"] == w_synced_only["total_bytes"]
    assert w_0["total_bytes"] - w_p["total_bytes"] == 16 * 4
    assert w_p["server_state_bytes"] == w_synced_only["server_state_bytes"]


def test_personal_state_checkpoint_roundtrip(problem, tmp_path):
    """None-stripped server/ef trees ride the path-manifest checkpoint
    bitwise (None subtrees simply have no leaves to save)."""
    from repro.checkpoint import restore, save
    spec = engine.method_spec("fedadam", **MS_KW, personal=("head",))
    loss = _two_leaf_loss(problem)
    step = jax.jit(engine.build_round_step(loss, spec))
    state = engine.init_state(jax.random.PRNGKey(0), _two_leaf_init(2.0),
                              spec, 4)
    loader = QuadraticLoader(problem, seed=0)
    state, _ = step(state, jax.tree.map(jnp.asarray, loader.round_batch(3)),
                    jax.random.PRNGKey(9))
    save(str(tmp_path), 1, state)
    out, step_no = restore(str(tmp_path), state)
    assert step_no == 1
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_flatten_with_path(state)[0],
            jax.tree_util.tree_flatten_with_path(out)[0]):
        assert pa == pb
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------- #
# loader plumbing: the 'labeled' leaf
# --------------------------------------------------------------------------- #


def test_federated_loader_labeled_leaf():
    d = ClassificationData.make(n=2000, n_classes=10)
    parts = main_class_partition(d.y, 4, 0.5)
    lab = labeled_mask(d.y, 0.2, seed=3)
    loader = FederatedLoader(d.x, d.y, parts, batch_size=8, labeled=lab)
    b = loader.round_batch(H=3)
    assert b["labeled"].shape == (4, 3, 8)
    assert set(np.unique(b["labeled"])) <= {0.0, 1.0}
    # default: no leaf — the pre-objectives two-leaf batch
    b0 = FederatedLoader(d.x, d.y, parts, batch_size=8).round_batch(H=3)
    assert set(b0.keys()) == {"x", "y"}


def test_lm_round_loader_labeled_leaf_round_addressable():
    stream = TokenStream(128, seed=0)
    loader = LMRoundLoader(stream, 2, 4, labeled_frac=0.25, seed=7)
    b1 = loader.round_batch(3, 2, 16)
    b2 = loader.round_batch(3, 2, 16)
    assert b1["labeled"].shape == (2, 2, 4)
    np.testing.assert_array_equal(b1["labeled"], b2["labeled"])
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b_other = loader.round_batch(4, 2, 16)
    assert not np.array_equal(b1["labeled"], b_other["labeled"])
    # fully labeled: structurally the pre-objectives batch
    full = LMRoundLoader(stream, 2, 4).round_batch(3, 2, 16)
    assert "labeled" not in full


def test_labeled_mask_stratified():
    rng = np.random.default_rng(0)
    y = rng.integers(0, 10, size=5000)
    m = labeled_mask(y, 0.1, seed=1)
    assert m.shape == y.shape and m.dtype == np.float32
    assert set(np.unique(m)) <= {0.0, 1.0}
    total = int(m.sum())
    assert abs(total - 500) <= 10
    for c in range(10):
        sel = m[y == c]
        assert sel.sum() >= 1                      # every class represented
        assert abs(sel.mean() - 0.1) < 0.03
    np.testing.assert_array_equal(m, labeled_mask(y, 0.1, seed=1))
    np.testing.assert_array_equal(labeled_mask(y, 1.0), np.ones_like(m))
    np.testing.assert_array_equal(labeled_mask(y, 0.0), np.zeros_like(m))


# --------------------------------------------------------------------------- #
# launch threading (tiny mesh)
# --------------------------------------------------------------------------- #


def test_build_train_step_threads_objective_and_personal():
    from jax.sharding import Mesh

    from repro.configs import ShapeConfig
    from repro.launch.steps import build_train_step

    dev = np.array(jax.devices("cpu")[:1]).reshape(1, 1)
    mesh = Mesh(dev, ("data", "model"))
    shape = ShapeConfig("tiny_train", 8, 2, "train")
    obj = objectives.ObjectiveSpec(kind="pseudo-label", unlabeled_weight=0.5)
    built = build_train_step("qwen2-0.5b", shape, mesh, method="fedadam",
                             reduced=True, h_local=2, objective=obj,
                             labeled_frac=0.25, personal=("final_norm",))
    assert built.meta["objective"] == {"kind": "pseudo-label",
                                       "labeled_frac": 0.25,
                                       "personal": ["final_norm"]}
    spec = built.meta["engine_spec"]
    assert spec.sync.personal == ("final_norm",)
    assert "labeled" in built.args[1]
    state_shape = built.args[0]
    # server state carries no personal leaves; spec trees align with shapes
    for path, _ in jax.tree_util.tree_flatten_with_path(
            state_shape["server"])[0]:
        assert "final_norm" not in "/".join(str(p) for p in path)
    state_spec, _ = built.in_shardings
    for k in state_shape:
        assert jax.tree.structure(state_shape[k]) \
            == jax.tree.structure(
                jax.tree.map(lambda s: s.spec, state_spec[k]))


def test_build_train_step_rejects_personal_global_precond():
    from jax.sharding import Mesh

    from repro.configs import ShapeConfig
    from repro.launch.steps import build_train_step

    dev = np.array(jax.devices("cpu")[:1]).reshape(1, 1)
    mesh = Mesh(dev, ("data", "model"))
    shape = ShapeConfig("tiny_train", 8, 2, "train")
    with pytest.raises(ValueError, match="personal"):
        build_train_step("qwen2-0.5b", shape, mesh, method="savic",
                         reduced=True, h_local=2, personal=("final_norm",))


# --------------------------------------------------------------------------- #
# end-to-end: semi-supervised MLP federation learns on a label-scarce split
# --------------------------------------------------------------------------- #


def _mlp(n_in, n_classes, width=32):
    def init(key):
        k1, k2 = jax.random.split(key)
        return {"w1": jax.random.normal(k1, (n_in, width)) * (n_in ** -0.5),
                "b1": jnp.zeros((width,)),
                "w2": jax.random.normal(k2, (width, n_classes))
                * (width ** -0.5),
                "b2": jnp.zeros((n_classes,))}

    def logits_fn(params, x):
        h = jax.nn.relu(x @ params["w1"] + params["b1"])
        return h @ params["w2"] + params["b2"]
    return init, logits_fn


@pytest.mark.filterwarnings("ignore:main_class_partition")
def test_semi_supervised_federation_learns():
    """Engine × objective × labeled-mask loader end to end: supervised CE on
    the labeled subset decreases over rounds on a main-class split with only
    10% labels."""
    data = ClassificationData.make(n=4000, n_classes=10, seed=0)
    parts = main_class_partition(data.y, 4, 0.3, seed=0)
    lab = labeled_mask(data.y, 0.1, seed=0)
    loader = FederatedLoader(data.x, data.y.astype(np.int32), parts,
                             batch_size=16, seed=0, labeled=lab)
    init, logits_fn = _mlp(data.x.shape[1], 10)
    obj = objectives.classification_objective(
        objectives.ObjectiveSpec(kind="consistency", unlabeled_weight=0.5,
                                 noise_sigma=0.1), logits_fn)
    spec = engine.method_spec("fedadam", eta_l=0.02, eta=0.05)
    step = jax.jit(engine.build_round_step(obj.base_loss, spec,
                                           objective=obj))
    state = engine.init_state(jax.random.PRNGKey(0), init, spec, 4)
    key = jax.random.PRNGKey(1)
    losses = []
    for _ in range(12):
        key, k = jax.random.split(key)
        batch = jax.tree.map(jnp.asarray, loader.round_batch(H=4))
        state, met = step(state, batch, k)
        losses.append(float(met["loss"]))
    assert losses[-1] < losses[0], losses
