"""Run one benchmark cell once on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout. The cell is an entry of ``BENCHMARK.json``.
Needs a TPU with at least the cell's chips, and exits non-zero without one.
Standard error carries the progress and, last, each number compared with
the plain reference beside its limit. The last line of standard output is
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``.
With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of a few
rounds taken after an untraced window.

JAX's persistent compilation cache is kept at ``<checkout>/.jax_cache``,
so only the first run of a cell in a checkout compiles.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise SystemExit(f"no program under {ROOT}/src/repro: run from a "
                         f"checkout of the repository")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

    import jax
    # before anything touches a device, so no cache is set up before it
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from benchmarks.chip import harness, spec

    cell = spec.load_cell(ROOT, args.workload)
    devices = jax.devices()
    dev = devices[0]
    print(f"device {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev.platform}")
    if len(devices) < cell.chips:
        raise SystemExit(f"{args.workload} needs {cell.chips} chips, JAX "
                         f"found {len(devices)}")
    with open(os.path.join(ROOT, "benchmarks", "chip", "peaks.json")) as f:
        peaks = json.load(f)
    if dev.device_kind not in peaks:
        raise SystemExit(f"no peaks for {dev.device_kind!r} in peaks.json")

    result = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                              devices[:cell.chips], T_START,
                              peaks[dev.device_kind])
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
