"""Per-step collective bytes of the shard-mapped fused local step — the
rows of BENCH_kernels_sharded.json (DESIGN.md §7, §11).

Standalone subprocess (the matrix harness's ``kernels_sharded`` bench in
benchmarks/run.py spawns it once and fans its record out over the ``plan``
axis): the main benchmark process keeps 1 CPU device, this worker forces 8
host devices and
lowers ONE local step of the flat-buffer pipeline under model-/FSDP-/mixed-
sharded plans, three arms per plan:

  * sharded — flatten -> fused kernel -> unflatten, all inside shard_map over
    the plan's shard axes (the live fast path).  Per-step collective bytes
    MUST be 0: nothing may touch the flat buffers.
  * naive   — the same step through the single global flat view (what the
    pre-PR launch gate guarded against): GSPMD reshards the whole client
    state, so its per-step collective bytes are the measured blowup.
  * tree    — the unfused per-leaf elementwise update (the fallback the old
    gate forced): also 0 collective bytes, the baseline the fused path must
    not regress.

Collective bytes are parsed from the optimized HLO (utils/hlo.collective_bytes
— compiled.cost_analysis() carries no collective key on this backend); HBM
"bytes accessed" per arm comes from xla_cost_properties.  Prints one line of
JSON to stdout.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"   # bytes are counted on virtual CPU devices

import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.engine import _shard_flat_ops
from repro.kernels import ref as kref
from repro.utils.flatten import FlatLayout, ShardedFlatPlan
from repro.utils.hlo import collective_bytes
from repro.utils.hlo_cost import xla_cost_properties

M = 4
# NB: tests/_fused_sharded_worker.py carries the same three-plan spec table
# and step builders on a smaller toy tree (its copy asserts, this one
# measures with leaves big enough that the naive reshard dominates); a
# change to the fused_step signature or the plan shapes must land in both.
# "bias" is the uneven (replicated-fallback) leaf
SHAPES = {"w1": (64, 512), "b1": (512,), "w2": (512, 256), "b2": (256,),
          "bias": (5,)}
PLANS = {
    "model": (None, ("model",),
              {"w1": P(None, "model"), "b1": P("model"),
               "w2": P("model", None), "b2": P("model"), "bias": P()}),
    "fsdp": (None, ("data", "model"),
             {"w1": P(None, ("data", "model")), "b1": P(("data", "model")),
              "w2": P(("data", "model"), None), "b2": P(("data", "model")),
              "bias": P()}),
    "mixed": (("data",), ("model",),
              {"w1": P(None, "model"), "b1": P("model"),
               "w2": P("model", None), "b2": P("model"), "bias": P()}),
}
KW = dict(gamma=0.01, beta1=0.9, weight_decay=0.0, alpha=1e-2, beta2=0.999,
          kind="adam", clip="max", schedule="const", update_d=True)


def _params(key):
    return {name: jax.random.normal(jax.random.fold_in(key, i), (M,) + shp)
            for i, (name, shp) in enumerate(SHAPES.items())}


def _measure(fn, args, in_sh, out_sh, mesh):
    with mesh:
        c = jax.jit(fn, in_shardings=in_sh,
                    out_shardings=out_sh).lower(*args).compile()
    coll, by_kind, _ = collective_bytes(c.as_text())
    cost = xla_cost_properties(c)
    return {"collective_bytes": int(coll),
            "collective_by_kind": {k: int(v) for k, v in by_kind.items()},
            "bytes_accessed": float(cost.get("bytes accessed", 0.0))}


def main():
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         devices=jax.devices()[:8])
    params = _params(jax.random.key(7))
    out = {"n_devices": 8, "clients": M,
           "leaves": {k: list(v) for k, v in SHAPES.items()},
           "plans": {}}
    for plan_name, (client, axes, pspecs) in PLANS.items():
        leaf_specs = {k: P(client, *tuple(pspecs[k])) for k in SHAPES}
        ns = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                    is_leaf=lambda x: isinstance(x, P))
        in_sh, out_sh = (ns(leaf_specs),), ns(leaf_specs)
        params_one = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape[1:], x.dtype), params)
        plan = ShardedFlatPlan.build(mesh, params_one, pspecs, axes,
                                     client=client)
        lay = plan.layout
        t0 = jnp.zeros((M,), jnp.int32)

        def sharded_step(tree):
            p = lay.flatten(tree, mesh, lead=(client,))
            _, _, _, _, fused_step = _shard_flat_ops(plan, local=True)
            po, _, _ = fused_step(p, p * 0.9, p * 0.1, p * 0.5 + 1.0, None,
                                  t0, None, **KW)
            return lay.unflatten(po, mesh, lead=(client,))

        glay = FlatLayout.for_tree(params, batch_dims=1)

        def naive_step(tree):
            p = glay.flatten(tree, batch_dims=1)
            po, _, _ = kref.fused_step_ref(
                p, p * 0.9, p * 0.1, p * 0.5 + 1.0, None, None, None,
                **dict(KW, update_d=False))
            return glay.unflatten(po, batch_dims=1)

        def tree_step(tree):
            return jax.tree.map(
                lambda p: p - 0.01 * (0.9 * p * 0.9 + p * 0.1)
                / jnp.maximum(1e-2, jnp.sqrt(jnp.abs(p * 0.5 + 1.0))), tree)

        rec = {
            "sharded": _measure(sharded_step, (params,), in_sh, out_sh, mesh),
            "naive": _measure(naive_step, (params,), in_sh, out_sh, mesh),
            "tree": _measure(tree_step, (params,), in_sh, out_sh, mesh),
            "n_shards": lay.n_shards, "n_local": lay.n_local,
        }
        # no ratio column: sharded/tree are pinned at exactly 0 collective
        # bytes, so the naive arm's ABSOLUTE per-step bytes are the blowup
        # (any denominator would fabricate a multiplier)
        out["plans"][plan_name] = rec
    print(json.dumps(out))


if __name__ == "__main__":
    main()
