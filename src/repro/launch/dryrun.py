import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × input-shape × mesh).

The two lines above MUST run before any other import (jax locks the device
count on first init); 512 placeholder host devices back the production meshes
(16,16) and (2,16,16).

Per pair this records into results/dryrun/<arch>__<shape>__<mesh>.json:
  * memory_analysis()  — per-device argument/output/temp/code bytes
  * cost_analysis()    — HLO FLOPs + bytes accessed (roofline numerators)
  * collective operand bytes by kind (parsed from optimized HLO)
  * compile wall time, mode (paper/plain), clients M, analytic param count

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  python -m repro.launch.dryrun --all                 # single-pod sweep
  python -m repro.launch.dryrun --all --multi-pod     # 2-pod sweep
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import get_config, get_shape, pairs_to_run
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step
from repro.utils.hlo import collective_bytes, op_census
from repro.utils.hlo_cost import analyze as hlo_analyze
from repro.utils.hlo_cost import xla_cost_properties


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            mode: str = "auto", method: str = "savic", compression=None,
            het_model=None, het_seed: int = 0, het_sigma: float = 0.6,
            asynchrony=None, controller=None, objective=None, labeled_frac: float = 1.0, personal=None,
            out_dir: str = "results/dryrun",
            save: bool = True, call=None, tag: str = "", verbose=True):
    mesh = make_production_mesh(multi_pod=multi_pod)
    shape = get_shape(shape_name)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "n_devices": mesh.devices.size, "tag": tag,
    }
    t0 = time.time()
    built = build_step(arch, shape_name, mesh, mode=mode, method=method,
                       compression=compression, het_model=het_model,
                       het_seed=het_seed, het_sigma=het_sigma,
                       asynchrony=asynchrony, controller=controller,
                       objective=objective, labeled_frac=labeled_frac,
                       personal=personal, call=call) \
        if shape.kind == "train" else build_step(arch, shape_name, mesh,
                                                 call=call)
    with mesh:
        jitted = jax.jit(built.fn, in_shardings=built.in_shardings,
                         out_shardings=built.out_shardings,
                         donate_argnums=built.donate)
        lowered = jitted.lower(*built.args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()

    mem = compiled.memory_analysis()
    cost = xla_cost_properties(compiled)  # list/dict normalized per jaxlib
    hlo = compiled.as_text()
    coll_total, coll_kind, coll_count = collective_bytes(hlo)
    tc = hlo_analyze(hlo)   # trip-count-corrected (scans execute L·H times)

    cfg = get_config(arch)
    rec.update({
        "kind": shape.kind,
        "mode": built.meta.get("mode", "serve"),
        "method": built.meta.get("method", ""),
        "clients": built.meta.get("clients", 0),
        "h_local": built.meta.get("h_local", 0),
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        # raw cost_analysis (while bodies counted ONCE — kept for reference)
        "flops_raw": cost.get("flops", 0.0),
        "bytes_raw": cost.get("bytes accessed", 0.0),
        # trip-count-corrected HLO analysis (the roofline numerators)
        "flops": tc["flops"],
        "bytes_accessed": tc["bytes"],
        "collective_bytes": tc["collective_bytes"],
        "collective_by_kind": tc["collective_by_kind"],
        "collective_counts": tc["collective_counts"],
        "unknown_trip_loops": tc["unknown_trip_loops"],
        "collective_bytes_static": coll_total,
        "collective_by_kind_static": coll_kind,
        "memory": {
            k: getattr(mem, k) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        },
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "op_census": op_census(hlo),
        "ok": True,
    })
    spec = built.meta.get("engine_spec")
    if spec is not None:
        # sync compression (engine SyncStrategy layer) + analytic wire volume
        import dataclasses as _dc

        from repro.core import engine as _engine
        params_one = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
            built.args[0]["params"])
        rec["compression"] = _dc.asdict(spec.sync.compression)
        rec["sync_payload_per_client"] = _engine.bytes_on_wire(spec, params_one)
        # heterogeneity & staleness (DESIGN.md §5): the H_m vector is a spec
        # constant (baked into the program); the buffer is server state
        rec["asynchrony"] = _dc.asdict(spec.sync.asynchrony)
        if "objective" in built.meta:
            # client objective & personalization (DESIGN.md §12): the kind,
            # labeled fraction and client-resident leaf mask the program was
            # lowered with — wire volume above already excludes personal
            # leaves (bytes_on_wire strips them)
            rec["objective"] = built.meta["objective"]
        hs = spec.client.local_steps
        rec["heterogeneity"] = {
            "local_steps": list(hs) if hs is not None else None,
            **{k: built.meta[k] for k in
               ("het_model", "step_times", "sim_round_time_sync",
                "sim_round_time_budgeted", "sim_round_time_async")
               if k in built.meta},
        }
        if spec.controller.enabled:
            # controller contract (DESIGN.md §10): the compiled program is
            # knob-agnostic — H_m/k/b_eff are read from state["ctrl"] each
            # round. The artifact records the spec and the INITIAL knobs;
            # the realized trajectory lands in launch/train.py's log.
            from repro.core import controller as _ctrl
            c0 = _ctrl.init_ctrl_state(spec.controller,
                                       built.meta.get("clients", 0))
            rec["controller"] = {
                "spec": _dc.asdict(spec.controller),
                "init_knobs": {
                    "h_m": [int(h) for h in c0["h_m"]],
                    "k": float(c0["k"]),
                    "b_eff": int(c0["b_eff"]),
                },
                "state_leaves": {k2: list(v.shape)
                                 for k2, v in c0.items()},
            }
    if verbose:
        print(f"[dryrun] {arch:18s} {shape_name:12s} mesh={rec['mesh']:8s} "
              f"mode={rec['mode']:6s} flops={rec['flops']:.3e} "
              f"coll={coll_total/1e9:.2f}GB compile={rec['compile_s']:.1f}s",
              flush=True)
    if save:
        os.makedirs(out_dir, exist_ok=True)
        name = f"{arch}__{shape_name}__{rec['mesh']}"
        if tag:
            name += f"__{tag}"
        with open(os.path.join(out_dir, name + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mode", default="auto")
    ap.add_argument("--method", default="savic",
                    help="round-engine method for train shapes "
                         "(savic|fedadagrad|fedadam|fedyogi|local-adam)")
    ap.add_argument("--compression", default="none",
                    help="sync delta compression for train shapes "
                         "(none|topk|randk|int8-stochastic)")
    ap.add_argument("--compression-k", type=float, default=0.1)
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--het-model", default="",
                    help="systems-heterogeneity model for train shapes "
                         "(uniform|lognormal|tiers); H_m is baked into the "
                         "lowered program as scan masking")
    ap.add_argument("--het-seed", type=int, default=0)
    ap.add_argument("--het-sigma", type=float, default=0.6,
                    help="lognormal straggler sigma for --het-model lognormal")
    ap.add_argument("--async-buffer", type=int, default=0,
                    help="server staleness buffer depth B (adds the sharded "
                         "delta FIFO to the compiled state)")
    ap.add_argument("--staleness-weight", default="constant")
    ap.add_argument("--controller", action="store_true",
                    help="enable the adaptive communication-budget controller "
                         "(round-addressable H_m/k/b_eff knobs; artifact "
                         "records the spec + initial knob values)")
    ap.add_argument("--objective", default="supervised",
                    help="client objective for train shapes "
                         "(supervised|consistency|pseudo-label)")
    ap.add_argument("--labeled-frac", type=float, default=1.0,
                    help="labeled fraction (<1 adds the 'labeled' batch leaf)")
    ap.add_argument("--personalize", default="",
                    help="comma-separated client-resident param-path "
                         "substrings (never synced; DESIGN.md §12)")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    from repro.core.engine import AsyncSpec, CompressionSpec
    comp = None if args.compression == "none" else CompressionSpec(
        op=args.compression, k=args.compression_k,
        error_feedback=args.error_feedback)
    asy = None if not args.async_buffer else AsyncSpec(
        buffer_rounds=args.async_buffer, weighting=args.staleness_weight)
    het = args.het_model or None
    ctrl = None
    if args.controller:
        from repro.core.controller import ControllerSpec
        ctrl = ControllerSpec(enabled=True, buffer_max=args.async_buffer)
        het = het or "lognormal"  # controller requires a heterogeneity trace
    obj = None
    if args.objective != "supervised":
        from repro.core.objectives import ObjectiveSpec
        obj = ObjectiveSpec(kind=args.objective)
    personal = tuple(p for p in args.personalize.split(",") if p) or None

    if args.all:
        failures = []
        for arch, shape in pairs_to_run():
            try:
                run_one(arch, shape, multi_pod=args.multi_pod, mode=args.mode,
                        method=args.method, compression=comp, het_model=het,
                        het_seed=args.het_seed, het_sigma=args.het_sigma,
                        asynchrony=asy, controller=ctrl,
                        objective=obj, labeled_frac=args.labeled_frac,
                        personal=personal,
                        out_dir=args.out, tag=args.tag)
            except Exception as e:  # noqa
                failures.append((arch, shape, repr(e)))
                print(f"[dryrun] FAIL {arch} {shape}: {e}", flush=True)
                traceback.print_exc()
        print(f"[dryrun] done; {len(failures)} failures")
        for f in failures:
            print("  FAIL:", *f)
        raise SystemExit(1 if failures else 0)

    run_one(args.arch, args.shape, multi_pod=args.multi_pod, mode=args.mode,
            method=args.method, compression=comp, het_model=het,
            het_seed=args.het_seed, het_sigma=args.het_sigma, asynchrony=asy,
            controller=ctrl, objective=obj, labeled_frac=args.labeled_frac,
            personal=personal, out_dir=args.out, tag=args.tag)


if __name__ == "__main__":
    main()
