"""Readings that set a cell's check limits, on the chip at the cell's size.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,...,12 \
        [--control-seeds 1,2,3] [--faults half_batch:1,2,3]

For each seed, the reference's check rounds, then the program's as the
configuration states it (its readings over a dozen seeds or more give each
number's lower reading). On the control seeds: the control, the program's
own bfloat16 compute path (bfloat16 being the nearest precision below the
configuration's float32), whose smallest readings give the upper one; and,
for the record, the reference at ``highest`` matmul precision put in the
program's place (``reference_highest``). On a fault's seeds, the program
with that fault planted (``faults.py``). No measured window: training's
readings need none.
Prints one JSON line per reading and, with ``--out``, writes them all,
with each leaf's gaps and the reference's leaf norms.
The benchmark's own runs never run this.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

LOWER = "bfloat16"


def _seeds(text):
    return [int(x) for x in text.split(",") if x]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--faults", default="",
                    help="name:seed,seed;name:seed,... of faults.py")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from benchmarks.chip import check, faults, harness, spec

    cell = spec.load_cell(ROOT, args.workload)
    devices = jax.devices()[:cell.chips]
    dev = devices[0]
    print(f"device {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    variants = [("program", None, None, args.seeds)]
    if args.control_seeds:
        variants += [("control", LOWER, None, args.control_seeds),
                     ("reference_highest", None, "reference",
                      args.control_seeds)]
    for part in filter(None, args.faults.split(";")):
        name, seeds = part.split(":")
        variants.append((name, None, faults.FAULTS[name], _seeds(seeds)))
    built = {}
    for name, dtype, fault, _ in variants:
        if fault is None:
            built[name] = harness.build_step(cell, devices, dtype=dtype)[0]
        elif fault != "reference":
            with fault():
                built[name] = harness.build_step(cell, devices)[0]
    limits = cell.workload["check"]["limits"]
    rows = []
    seeds = sorted({s for v in variants for s in v[3]})
    for seed in seeds:
        t = time.perf_counter()
        ref = harness.reference_side(cell, devices, seed)
        gc.collect()
        t_ref = time.perf_counter() - t
        for name, dtype, fault, vseeds in variants:
            if seed not in vseeds:
                continue
            if fault == "reference":
                read = harness.reference_side(cell, devices, seed, "highest")
            else:
                state, _, read = harness.check_rounds(built[name], cell, seed)
                del state
            gc.collect()
            nums = check.numbers(read, ref)
            row = {"workload": cell.name, "variant": name, "seed": seed,
                   "numbers": nums, "passes": check.verdict(nums, limits),
                   "losses": read["losses"], "ref_losses": ref["losses"],
                   "leaf_gaps": check.leaf_gaps(read, ref),
                   "ref_grad": ref["grad"], "ref_change": ref["change"],
                   "reference_s": t_ref}
            rows.append(row)
            print(json.dumps({k: v for k, v in row.items() if k not in
                              ("leaf_gaps", "ref_grad", "ref_change")}),
                  flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"device": {"platform": dev.platform,
                                  "kind": dev.device_kind,
                                  "count": len(devices)},
                       "leaves": harness.leaf_names(cell.config),
                       "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
