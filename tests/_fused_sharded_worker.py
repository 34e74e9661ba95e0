"""Subprocess worker for the shard-mapped fused-step differential suite
(tests/test_fused_sharded.py; same pattern as tests/_sharding_worker.py —
jax locks the device count at first init, so the main pytest process keeps 1
device and this worker gets 8).

Modes (argv[1]):
  fast   representative slice: {savic, fedadam, local-adam} on the mixed
         client×model plan + the clip/wd/H_m composition + the shard_map
         flatten/unflatten-vs-reference pin.
  full   all six METHODS × {model, fsdp, mixed} plans (tier-2 @slow).
  hlo    collective-byte pins: the isolated per-step flat program carries
         ZERO collective bytes, the fused round program's collective bytes
         equal the tree path's, and the naive global flat view measurably
         blows up.  Prints one "RESULT {json}" line.

Every differential case asserts BITWISE (fp32) equality of the full state
trajectory: shard-mapped fused vs the live tree path vs the verbatim pre-PR
engine snapshot (tests/_reference_engine.py), all three jitted with the SAME
state/batch shardings on the same (2, 4) = ('data', 'model') mesh.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"   # 8 virtual CPU devices, kernels interpreted

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import _reference_engine as ref_engine
from repro.core import engine, savic
from repro.core.preconditioner import PrecondConfig
from repro.utils.flatten import FlatLayout, ShardedFlatPlan

M, H, B_MICRO = 4, 3, 2
MS_KW = dict(gamma=0.01, alpha=1e-2, eta_l=0.01, eta=0.05)

# toy MLP whose leaves exercise every layout case: dim-0 and dim-1 splits,
# divisible 1-D leaves, and an uneven leaf (5 % {4, 8} != 0 -> replicated
# fallback in every shard block)
LEAVES = ("w1", "b1", "w2", "b2", "u")


def init(key):
    ks = jax.random.split(key, 3)
    return {"w1": jax.random.normal(ks[0], (6, 16)) * 0.3,
            "b1": jnp.zeros((16,)),
            "w2": jax.random.normal(ks[1], (16, 8)) * 0.3,
            "b2": jnp.zeros((8,)),
            "u": jax.random.normal(ks[2], (5,))}


def loss(params, micro):
    h = jnp.tanh(micro["x"] @ params["w1"] + params["b1"])
    y = h @ params["w2"] + params["b2"]
    return jnp.mean((y - micro["y"]) ** 2) + 1e-3 * micro["z"] @ params["u"]


# plan name -> (client axes entry | None, shard axes, single-replica pspecs)
# NB: benchmarks/sharded_collectives.py carries the same plan table and step
# builders on bigger leaves (this copy asserts, that one measures); keep the
# two in sync when the fused_step signature or plan shapes change.
PLANS = {
    # pure tensor parallel: clients replicated over 'data'
    "model": (None, ("model",),
              {"w1": P(None, "model"), "b1": P("model"),
               "w2": P("model", None), "b2": P("model"), "u": P()}),
    # FSDP over both axes jointly (8 shards), clients replicated
    "fsdp": (None, ("data", "model"),
             {"w1": P(None, ("data", "model")), "b1": P(("data", "model")),
              "w2": P(("data", "model"), None), "b2": P(("data", "model")),
              "u": P()}),
    # mixed client×model: M over 'data', shards over 'model'
    "mixed": (("data",), ("model",),
              {"w1": P(None, "model"), "b1": P("model"),
               "w2": P("model", None), "b2": P("model"), "u": P()}),
}


def batch_for(key, b=B_MICRO):
    ks = jax.random.split(key, 3)
    return {"x": jax.random.normal(ks[0], (M, H, b, 6)),
            "y": jax.random.normal(ks[1], (M, H, b, 8)),
            "z": jax.random.normal(ks[2], (M, H, 5)) * 0.1}


def state_specs(state, pspecs, client):
    """Engine state pspec tree per DESIGN.md §2 for the toy tree."""
    cl = client
    pspec_m = {k: P(cl, *tuple(pspecs[k])) for k in LEAVES}
    spec = {"params": pspec_m, "mom": dict(pspec_m), "round": P()}
    pc = {"t": P(cl) if state["precond"]["t"].ndim else P()}
    if "d" in state["precond"]:
        local = jax.tree.leaves(state["precond"]["d"])[0].ndim \
            > jax.tree.leaves(state["params"])[0].ndim - 1
        pc["d"] = dict(pspec_m) if local else {k: pspecs[k] for k in LEAVES}
    spec["precond"] = pc
    if "server" in state:
        one = {k: pspecs[k] for k in LEAVES}
        spec["server"] = {"m": one, "v": dict(one)}
    return spec


def run_case(mesh, plan_name, spec, eng, shard_plan=None, rounds=3):
    client, _, pspecs = PLANS[plan_name]
    if shard_plan is not None:
        step = eng.build_round_step(loss, spec, shard_plan)
    else:
        step = eng.build_round_step(loss, spec)
    state = eng.init_state(jax.random.PRNGKey(0), init, spec, M)
    sspec = state_specs(state, pspecs, client)
    bspec = {"x": P(client, None, None, None), "y": P(client, None, None, None),
             "z": P(client, None, None)}
    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    with mesh:
        jstep = jax.jit(step, in_shardings=(ns(sspec), ns(bspec), None),
                        out_shardings=(ns(sspec), None))
        key = jax.random.PRNGKey(1)
        for _ in range(rounds):
            key, k1, k2 = jax.random.split(key, 3)
            state, met = jstep(state, batch_for(k1), k2)
    return state, met


def assert_state_bitwise(st_a, st_b, tag):
    for k in LEAVES:
        np.testing.assert_array_equal(np.asarray(st_a["params"][k]),
                                      np.asarray(st_b["params"][k]),
                                      err_msg=f"{tag} params/{k}")
        np.testing.assert_array_equal(np.asarray(st_a["mom"][k]),
                                      np.asarray(st_b["mom"][k]),
                                      err_msg=f"{tag} mom/{k}")
        if "d" in st_b["precond"]:
            np.testing.assert_array_equal(
                np.asarray(st_a["precond"]["d"][k]),
                np.asarray(st_b["precond"]["d"][k]),
                err_msg=f"{tag} d/{k}")
    np.testing.assert_array_equal(np.asarray(st_a["precond"]["t"]),
                                  np.asarray(st_b["precond"]["t"]), err_msg=tag)
    if "server" in st_b:
        for k in LEAVES:
            np.testing.assert_array_equal(np.asarray(st_a["server"]["m"][k]),
                                          np.asarray(st_b["server"]["m"][k]),
                                          err_msg=f"{tag} server.m/{k}")
            np.testing.assert_array_equal(np.asarray(st_a["server"]["v"][k]),
                                          np.asarray(st_b["server"]["v"][k]),
                                          err_msg=f"{tag} server.v/{k}")


def build_plan(mesh, plan_name):
    client, axes, pspecs = PLANS[plan_name]
    params_one = jax.eval_shape(init, jax.random.PRNGKey(0))
    return ShardedFlatPlan.build(mesh, params_one, pspecs, axes, client=client)


def diff_one(mesh, plan_name, method):
    plan = build_plan(mesh, plan_name)
    spec_f = engine.method_spec(method, **MS_KW, use_fused_kernel=True)
    spec_u = engine.method_spec(method, **MS_KW)
    spec_r = ref_engine.method_spec(method, **MS_KW)
    st_f, met_f = run_case(mesh, plan_name, spec_f, engine, shard_plan=plan)
    st_u, met_u = run_case(mesh, plan_name, spec_u, engine)
    st_r, met_r = run_case(mesh, plan_name, spec_r, ref_engine)
    tag = f"{plan_name}/{method}"
    assert_state_bitwise(st_f, st_u, tag + " fused-vs-tree")
    assert_state_bitwise(st_f, st_r, tag + " fused-vs-ref")
    assert float(met_f["loss"]) == float(met_u["loss"]) == float(met_r["loss"])
    print(f"OK diff {tag}", flush=True)


def diff_composition(mesh, plan_name):
    """Heterogeneous H_m composes with the shard-mapped path BITWISE: the
    mask is a pure ``where``-select on the flat buffers (no new multiply-add,
    nothing reduces across shards), and frozen clients freeze their per-shard
    flat state at exactly step H_m."""
    plan = build_plan(mesh, plan_name)
    pc = PrecondConfig(kind="adam", alpha=1e-2)
    mk = lambda fused: savic.engine_spec(pc, savic.SavicConfig(
        gamma=0.01, beta1=0.9, scaling="local", use_fused_kernel=fused,
        local_steps=(2, 1, 3, 3)))
    st_f, _ = run_case(mesh, plan_name, mk(True), engine, shard_plan=plan)
    st_u, _ = run_case(mesh, plan_name, mk(False), engine)
    assert_state_bitwise(st_f, st_u, f"{plan_name}/hm")
    np.testing.assert_array_equal(np.asarray(st_f["precond"]["t"]),
                                  3 * np.asarray([2, 1, 3, 3]))
    print(f"OK diff {plan_name}/hm", flush=True)


def diff_clip_wd_composition(mesh, plan_name):
    """grad-clip + weight-decay composition: 1-ulp tolerance, NOT bitwise.

    Both knobs introduce ops whose lowering XLA:CPU may contract differently
    into the two differently-shaped programs: the clip's global grad-norm is
    the one cross-shard REDUCTION in the local step (per-device partial-sum
    order unpinned), and ``g + wd·p`` is a fresh multiply-add that may or may
    not become an FMA inside the shard_map body.  Same effect class as the
    jit-vs-jit FMA note in tests/test_fused_step.py — the elementwise
    flat-path contract itself stays bitwise (every other case in this
    worker, all six METHODS included)."""
    plan = build_plan(mesh, plan_name)
    pc = PrecondConfig(kind="adam", alpha=1e-2)
    mk = lambda fused: savic.engine_spec(pc, savic.SavicConfig(
        gamma=0.01, beta1=0.9, scaling="local", use_fused_kernel=fused,
        grad_clip=0.3, weight_decay=0.05, local_steps=(2, 1, 3, 3)))
    st_f, _ = run_case(mesh, plan_name, mk(True), engine, shard_plan=plan)
    st_u, _ = run_case(mesh, plan_name, mk(False), engine)
    for k in LEAVES:
        np.testing.assert_allclose(np.asarray(st_f["params"][k]),
                                   np.asarray(st_u["params"][k]),
                                   rtol=2e-5, atol=1e-7,
                                   err_msg=f"{plan_name}/clip-wd params/{k}")
        np.testing.assert_allclose(np.asarray(st_f["precond"]["d"][k]),
                                   np.asarray(st_u["precond"]["d"][k]),
                                   rtol=2e-5, atol=1e-7,
                                   err_msg=f"{plan_name}/clip-wd d/{k}")
    print(f"OK diff {plan_name}/clip-wd-hm (1-ulp)", flush=True)


def flatten_oracle(mesh):
    """shard_map flatten/unflatten == the mesh-free reference, bitwise, on
    every plan — incl. the uneven/replicated leaf."""
    tree = {k: jax.random.normal(jax.random.fold_in(jax.random.key(3), i),
                                 (M,) + s)
            for i, (k, s) in enumerate(
                {"w1": (6, 16), "b1": (16,), "w2": (16, 8), "b2": (8,),
                 "u": (5,)}.items())}
    for plan_name, (client, axes, pspecs) in PLANS.items():
        lay = build_plan(mesh, plan_name).layout
        lead = (client,)
        tree_s = jax.device_put(tree, jax.tree.map(
            lambda s: NamedSharding(mesh, P(client, *tuple(s))), pspecs,
            is_leaf=lambda x: isinstance(x, P)))
        with mesh:
            buf = jax.jit(lambda t: lay.flatten(t, mesh, lead=lead))(tree_s)
            back = jax.jit(lambda b: lay.unflatten(b, mesh, lead=lead))(buf)
        ref_buf = lay.flatten_ref(tree, batch_dims=1)
        assert buf.shape == (M, lay.n_flat)
        np.testing.assert_array_equal(np.asarray(buf), np.asarray(ref_buf),
                                      err_msg=f"{plan_name} flatten")
        for k in tree:
            np.testing.assert_array_equal(np.asarray(back[k]),
                                          np.asarray(tree[k]),
                                          err_msg=f"{plan_name} unflatten/{k}")
        print(f"OK flatten-oracle {plan_name}", flush=True)


def hlo_pins(mesh):
    """Collective-byte pins for the sharded fast path (DESIGN.md §7):

      * the isolated per-step flat program (flatten -> fused kernel ->
        unflatten) carries ZERO collective bytes;
      * the full fused round program's trip-corrected collective bytes EQUAL
        the tree path's (sync traffic only — nothing touches the flat
        buffers);
      * the naive global flat view (pre-PR reason for the gate) measurably
        reshards: its one-step program carries collective bytes.
    """
    from repro.kernels import ref as kref
    from repro.utils.hlo import collective_bytes
    from repro.utils.hlo_cost import analyze as hlo_analyze

    plan_name = "mixed"
    client, axes, pspecs = PLANS[plan_name]
    plan = build_plan(mesh, plan_name)
    lay = plan.layout
    ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                is_leaf=lambda x: isinstance(x, P))
    params = jax.tree.map(
        lambda l: jnp.broadcast_to(l[None], (M,) + l.shape),
        init(jax.random.PRNGKey(0)))
    leaf_specs = {k: P(client, *tuple(pspecs[k])) for k in LEAVES}
    params = jax.device_put(params, ns(leaf_specs))
    kw = dict(gamma=0.01, beta1=0.9, weight_decay=0.0, alpha=1e-2,
              beta2=0.999, kind="adam", clip="max", schedule="const",
              update_d=True)
    rec = {}

    # -- isolated per-step flat program: must carry ZERO collectives ---------
    def flat_step(tree):
        p = lay.flatten(tree, mesh, lead=(client,))
        from repro.core.engine import _shard_flat_ops
        _, _, _, _, fused_step = _shard_flat_ops(plan, local=True)
        po, mo, do = fused_step(p, p * 0.9, p * 0.1, p * 0.5 + 1.0, None,
                                jnp.zeros((M,), jnp.int32), None, **kw)
        return lay.unflatten(po, mesh, lead=(client,))

    with mesh:
        c = jax.jit(flat_step, in_shardings=(ns(leaf_specs),),
                    out_shardings=ns(leaf_specs)).lower(params).compile()
    total, by_kind, _ = collective_bytes(c.as_text())
    rec["step_collective_bytes_sharded"] = int(total)
    rec["step_collective_by_kind_sharded"] = {k: int(v)
                                              for k, v in by_kind.items()}

    # -- naive global flat view: the resharding blowup the gate guarded -----
    glay = FlatLayout.for_tree(params, batch_dims=1)

    def naive_step(tree):
        p = glay.flatten(tree, batch_dims=1)
        po, mo, _ = kref.fused_step_ref(p, p * 0.9, p * 0.1, p * 0.5 + 1.0,
                                        None, None, None, **dict(kw,
                                        update_d=False, schedule="const"))
        return glay.unflatten(po, batch_dims=1)

    with mesh:
        c = jax.jit(naive_step, in_shardings=(ns(leaf_specs),),
                    out_shardings=ns(leaf_specs)).lower(params).compile()
    total_naive, _, _ = collective_bytes(c.as_text())
    rec["step_collective_bytes_naive"] = int(total_naive)

    # -- full round program: fused collective bytes == tree path's ----------
    def coll_of(spec, shard_plan=None):
        step = engine.build_round_step(loss, spec, shard_plan)
        state = engine.init_state(jax.random.PRNGKey(0), init, spec, M)
        sspec = state_specs(state, pspecs, client)
        bspec = {"x": P(client, None, None, None),
                 "y": P(client, None, None, None), "z": P(client, None, None)}
        with mesh:
            c = jax.jit(step, in_shardings=(ns(sspec), ns(bspec), None),
                        out_shardings=(ns(sspec), None)).lower(
                state, batch_for(jax.random.PRNGKey(1)),
                jax.random.PRNGKey(2)).compile()
        return hlo_analyze(c.as_text())["collective_bytes"]

    spec_f = engine.method_spec("local-adam", **MS_KW, use_fused_kernel=True)
    spec_u = engine.method_spec("local-adam", **MS_KW)
    rec["round_collective_bytes_fused"] = coll_of(spec_f, plan)
    rec["round_collective_bytes_tree"] = coll_of(spec_u)
    print("RESULT " + json.dumps(rec), flush=True)


def main(mode: str):
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         devices=jax.devices()[:8])
    if mode == "fast":
        flatten_oracle(mesh)
        for method in ("savic", "fedadam", "local-adam"):
            diff_one(mesh, "mixed", method)
        diff_composition(mesh, "mixed")
    elif mode == "full":
        for plan_name in PLANS:
            for method in engine.METHODS:
                diff_one(mesh, plan_name, method)
            diff_composition(mesh, plan_name)
            diff_clip_wd_composition(mesh, plan_name)
    elif mode == "hlo":
        hlo_pins(mesh)
    else:
        raise SystemExit(f"unknown mode {mode!r}")
    print(f"ALL-OK {mode}")


if __name__ == "__main__":
    main(sys.argv[1])
