"""The one-pass sync (kernels/sync_average.py, engine._sync_one_pass): the
averaged params and momentum against the jnp sync (bitwise at M = 2, fp32
rounding otherwise), the drift against ``client_drift``, aliasing, personal
leaves, which leaves ``sync_plan`` gives the kernel, and that only a round
step built for one device runs it. Kernels run interpreted on the CPU."""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _reference_engine as ref_engine
from repro.core import engine
from repro.kernels import ops as kops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one replica's tree: a layer stack and a table that tile to (8, 128) (the
# kernel, layer-major and in place), and three leaves that do not (jnp)
SHAPES = {"stack": (3, 16, 256), "table": (40, 384), "heads": (8, 3, 128),
          "bias": (24,), "odd": (5, 130)}
KERNEL_LEAVES = ("stack", "table")


def _tree_m(M, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(SHAPES))
    return {k: jax.random.normal(kk, (M,) + s, jnp.float32)
            for kk, (k, s) in zip(keys, SHAPES.items())}


def _sync_kernels(fn, *args) -> int:
    """The one-pass sync kernels in ``fn``'s jaxpr, nested jaxprs included."""
    def walk(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += "sync_average" in str(eqn.params.get("name"))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += walk(sub)
        return n
    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


def _check_close(got, want, M, rtol=1e-6):
    for k in want:
        if M == 2:
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(want[k]), err_msg=k)
        else:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]), rtol=rtol,
                                       atol=1e-6, err_msg=k)


@pytest.mark.parametrize("M", [2, 3, 4])
@pytest.mark.parametrize("participation", [1.0, 0.5])
def test_one_pass_matches_jnp_sync(M, participation):
    """Every client slot of every synced leaf holds the jnp sync's weighted
    average (bitwise at M = 2; fp32 rounding otherwise); the drift is
    ``client_drift``'s to 1e-5; without drift the average is the same."""
    sy = engine.SyncSpec(participation=participation)
    key = jax.random.PRNGKey(5)
    tree = _tree_m(M)

    def one_pass(t, drift):
        w = engine.participation_weights(sy, key, M)
        return engine._sync_one_pass(t, w, engine.make_sync(sy, key, M), (),
                                     drift=drift)

    def jnp_sync(t):
        avg = engine.make_sync(sy, key, M)
        return (engine._broadcast_back(t, jax.tree.map(avg, t)),
                engine.client_drift(t))

    got, drift = jax.jit(lambda t: one_pass(t, True))(tree)
    want, want_drift = jax.jit(jnp_sync)(tree)
    _check_close(got, want, M)
    np.testing.assert_allclose(float(drift), float(want_drift), rtol=1e-5)
    got_mom, none = jax.jit(lambda t: one_pass(t, False))(tree)
    assert none is None
    _check_close(got_mom, want, M)
    assert _sync_kernels(lambda t: one_pass(t, True), tree) \
        == len(KERNEL_LEAVES)


@pytest.mark.parametrize("layer_major", [False, True])
def test_kernel_modes_and_aliasing(layer_major):
    """In place, the kernel's output aliases its input (no second (M, …)
    buffer); layer-major, it writes the (L, …) average once. Both match the
    jnp average bitwise at M = 2, and tail blocks keep out of the drift."""
    M = 2
    x = jax.random.normal(jax.random.PRNGKey(1), (M, 3, 40, 384))
    w = jnp.full((M,), 0.5)
    fn = lambda x, w: kops.sync_average(x, w, drift=True,
                                        layer_major=layer_major)
    jaxpr = jax.make_jaxpr(fn)(x, w)
    calls = [e for e in jaxpr.eqns[0].params["jaxpr"].eqns
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    aliases = tuple(calls[0].params["input_output_aliases"])
    assert aliases == (() if layer_major else ((1, 0),))
    out_avals = [v.aval.shape for v in calls[0].outvars]
    assert out_avals[0] == ((3, 40, 384) if layer_major else (1, M, 120, 384))
    from repro.kernels import sync_average as sa
    out, drift = jax.jit(lambda x, w: sa.sync_average(
        x, w, drift=True, layer_major=layer_major, block_bytes=M * 16 * 256
        * 4, interpret=True))(x, w)      # 16x256 blocks: tails on both dims
    want = (x * 0.5).sum(0)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(jnp.broadcast_to(want, x.shape)))
    np.testing.assert_allclose(float(drift), float(
        jnp.sum((x - x.mean(0)) ** 2)), rtol=1e-5)


def test_one_pass_rejects_leaves_that_do_not_tile():
    for shape in [(2, 24), (2, 5, 130), (2, 8, 3, 128)]:
        assert not kops.sync_tiles(shape, jnp.float32)
    assert not kops.sync_tiles((2, 16, 256), jnp.bfloat16)
    with pytest.raises(ValueError):
        kops.sync_average(jnp.zeros((2, 5, 130)), jnp.full((2,), 0.5))
    with pytest.raises(ValueError):
        kops.sync_average(jnp.zeros((2, 16, 256)), jnp.full((2,), 0.5),
                          layer_major=True)


# --------------------------------------------------------------------------- #
# the round step: against the pre-change engine, and with personal leaves
# --------------------------------------------------------------------------- #


def _init(key):
    ks = jax.random.split(key, 3)
    return {"stack": jax.random.normal(ks[0], (3, 128, 128)) * 0.05,
            "table": jax.random.normal(ks[1], (16, 128)) * 0.1,
            "bias": jax.random.normal(ks[2], (128,)) * 0.01}


def _loss(params, micro):
    h = micro["x"]
    for layer in range(3):
        h = jnp.tanh(h @ params["stack"][layer] + params["bias"])
    logits = h @ params["table"].T
    return jnp.mean((logits - micro["y"]) ** 2)


def _batch(M, H=2, b=4, seed=3):
    kx, ky = jax.random.split(jax.random.PRNGKey(seed))
    return {"x": jax.random.normal(kx, (M, H, b, 128)),
            "y": jax.random.normal(ky, (M, H, b, 16))}


def _rounds(build, init_state, spec, M, rounds=2):
    step = jax.jit(build(_loss, spec))
    state = init_state(jax.random.PRNGKey(0), _init, spec, M)
    metrics = []
    for r in range(rounds):
        state, met = step(state, _batch(M, seed=r), jax.random.PRNGKey(r))
        metrics.append(met)
    return state, metrics, step


@pytest.mark.parametrize("M", [2, 4])
@pytest.mark.parametrize("method", ["savic", "fedavg"])
def test_round_step_matches_pre_change_engine(M, method):
    """The round step on one device runs the kernel on the leaves that tile
    and matches the engine snapshot without it: params and momentum bitwise
    at M = 2, to fp32 rounding at M = 4; the drift to 1e-5."""
    kw = dict(gamma=0.05, eta_l=0.05)
    spec = engine.method_spec(method, **kw)
    st, met, step = _rounds(engine.build_round_step, engine.init_state,
                            spec, M)
    st_ref, met_ref, _ = _rounds(ref_engine.build_round_step,
                                 ref_engine.init_state,
                                 ref_engine.method_spec(method, **kw), M)
    _check_close(st["params"], st_ref["params"], M, rtol=1e-5)
    _check_close(st["mom"], st_ref["mom"], M, rtol=1e-5)
    for a, b in zip(met, met_ref):
        np.testing.assert_allclose(float(a["client_drift"]),
                                   float(b["client_drift"]), rtol=1e-5)
        np.testing.assert_array_equal(float(a["loss"]), float(b["loss"]))
    state = engine.init_state(jax.random.PRNGKey(0), _init, spec, M)
    n_trees = 2 if spec.sync.average_momentum else 1
    assert _sync_kernels(step, state, _batch(M), jax.random.PRNGKey(0)) \
        == 2 * n_trees


def test_personal_leaves_keep_each_clients_value():
    """A personal leaf is neither averaged nor broadcast: each client keeps
    its own copy, exactly as on the jnp sync (``sync_dtype="float32"`` takes
    that path with the same arithmetic); the synced leaves agree across
    clients."""
    M = 2
    spec = engine.method_spec("savic", scaling="local", gamma=0.05,
                              personal=("table",))
    jnp_spec = engine.method_spec("savic", scaling="local", gamma=0.05,
                                  personal=("table",), sync_dtype="float32")
    st, met, step = _rounds(engine.build_round_step, engine.init_state,
                            spec, M)
    st_j, met_j, _ = _rounds(engine.build_round_step, engine.init_state,
                             jnp_spec, M)
    table = np.asarray(st["params"]["table"])
    assert not np.array_equal(table[0], table[1])
    for tree in ("params", "mom"):
        _check_close(st[tree], st_j[tree], M)
        for k in ("stack", "bias"):
            a = np.asarray(st[tree][k])
            np.testing.assert_array_equal(a[0], a[1])
    for a, b in zip(met, met_j):
        np.testing.assert_allclose(float(a["client_drift"]),
                                   float(b["client_drift"]), rtol=1e-5)
    state = engine.init_state(jax.random.PRNGKey(0), _init, spec, M)
    assert _sync_kernels(step, state, _batch(M),
                         jax.random.PRNGKey(0)) == 2     # stack, twice


# --------------------------------------------------------------------------- #
# sync_plan, the set-up line, and the mesh path
# --------------------------------------------------------------------------- #


def _tiny_params(kind):
    from benchmarks.chip import program
    from benchmarks.chip.tests import tiny
    return program.param_shapes(program.model_config(tiny.cell(kind).config))


def _bytes(shapes):
    return sum(4 * math.prod(s) for s in shapes)


def test_sync_plan_tiny_cells():
    """By shape alone: the tiny qwen2's widths (112, 224) tile nowhere, so
    every leaf keeps the jnp sync; the tiny mamba2's projections and
    embeddings (128, 256 wide) take the kernel."""
    spec = engine.method_spec("savic")
    qwen = _tiny_params("qwen2")
    plan = engine.sync_plan(qwen, spec)
    assert plan["kernel"] == [] and plan["kernel_bytes"] == 0
    assert len(plan["jnp"]) == len(jax.tree.leaves(qwen))
    assert plan["jnp_bytes"] == _bytes(l.shape for l in jax.tree.leaves(qwen))
    mamba = _tiny_params("mamba2")
    plan = engine.sync_plan(mamba, spec)
    assert plan["kernel"] == [
        "blocks/stack/mamba/wo/w", "blocks/stack/mamba/wx/w",
        "blocks/stack/mamba/wz/w", "embed/head", "embed/table"]
    assert plan["kernel_bytes"] == _bytes(
        [(2, 256, 128), (2, 128, 256), (2, 128, 256), (128, 2048),
         (2048, 128)])
    assert plan["kernel_bytes"] + plan["jnp_bytes"] == _bytes(
        l.shape for l in jax.tree.leaves(mamba))


def test_sync_plan_qwen2_published_widths():
    """Cell 1's 16-layer qwen2-0.5b: the FFN, o-projection, norms and the
    table take the kernel; q/k/v (last dim 64) and their biases do not."""
    from repro.configs import get_config
    from repro.models import ModelCallConfig, build
    cfg = get_config("qwen2-0.5b").replace(n_layers=16)
    params = jax.eval_shape(build(cfg, ModelCallConfig()).init,
                            jax.random.PRNGKey(0))
    plan = engine.sync_plan(params, engine.method_spec("savic"))
    s = "blocks/stack/"
    assert plan["kernel"] == [
        s + "attn/wo/w", s + "ffn/wd/w", s + "ffn/wg/w", s + "ffn/wu/w",
        s + "norm1/scale", s + "norm2/scale", "embed/table"]
    assert plan["kernel_bytes"] == _bytes(
        [(16, 14, 64, 896)] + [(16, 4864, 896)] * 3 + [(16, 896)] * 2
        + [(153600, 896)])
    assert plan["jnp"] == [
        s + "attn/wk/b", s + "attn/wk/w", s + "attn/wq/b", s + "attn/wq/w",
        s + "attn/wv/b", s + "attn/wv/w", "final_norm/scale"]
    assert plan["kernel_bytes"] > 0.95 * (plan["kernel_bytes"]
                                          + plan["jnp_bytes"])


@pytest.mark.parametrize("case", ["mesh", "adaptive", "sync_dtype", "topk",
                                  "async", "personal"])
def test_sync_plan_paths_without_the_kernel(case):
    """Several devices, an adaptive server, a low-precision or compressed
    or buffered sync keep every leaf on the jnp sync; personal leaves are
    not synced and appear in neither list."""
    params = {k: jax.ShapeDtypeStruct(s, jnp.float32)
              for k, s in SHAPES.items()}
    spec = {"mesh": engine.method_spec("savic"),
            "adaptive": engine.method_spec("fedadam"),
            "sync_dtype": engine.method_spec("savic", sync_dtype="bfloat16"),
            "topk": engine.method_spec("fedavg", compression="topk",
                                       compression_k=0.5),
            "async": engine.method_spec("fedavg", async_buffer=2),
            "personal": engine.method_spec("fedavg", personal=("table",)),
            }[case]
    mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model")) \
        if case == "mesh" else None
    plan = engine.sync_plan(params, spec, mesh)
    if case == "personal":
        assert plan["kernel"] == ["stack"]
        assert "table" not in plan["jnp"] and len(plan["jnp"]) == 3
    else:
        assert plan["kernel"] == [] and len(plan["jnp"]) == len(SHAPES)


def test_train_setup_reports_the_sync_plan():
    """``launch/train.py`` reports the plan in its set-up record."""
    from repro.launch import train as train_mod
    log = train_mod.main(["--arch", "mamba2-1.3b", "--reduced", "--rounds",
                          "1", "--layers", "1", "--h-local", "1",
                          "--clients", "2", "--batch", "1", "--seq", "32"])
    assert log.setup["sync_plan"]["kernel"] == 5
    assert log.setup["sync_plan"]["jnp"] == 12
    assert np.isfinite(log[0]["loss"])


def test_mesh_path_emits_no_sync_kernel():
    """``steps.build_train_step`` on a 2x2 mesh of host devices lowers a
    round step with no sync kernel (GSPMD would gather the client-sharded
    state into it); the same step built for one device runs it."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_sync_mesh_worker.py")],
        capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT ")]
    res = json.loads(line[-1][len("RESULT "):])
    assert res["clients"] == 2
    assert res["mesh_kernel"] is False
    assert res["one_device_kernel"] is True
    assert res["plan_mesh"]["kernel"] == []
    assert len(res["plan_one"]["kernel"]) == 5
