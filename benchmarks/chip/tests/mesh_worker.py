"""Runs the qwen2 cell at the tiny size with four clients on four virtual
CPU devices, one client a chip, sound and with each fault planted, and
prints what came out as one JSON line. ``test_mesh.py`` starts it in a process of its own, with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` set before JAX
starts."""
import contextlib
import json
import time

import jax
import numpy as np

from benchmarks.chip import check, faults, harness
from benchmarks.chip.tests import tiny
from repro.configs import get_config

SEED = 2 ** 31 + 17


def main():
    devices = jax.devices()[:4]
    cell = tiny.cell("qwen2", clients=4)
    out = {"devices": len(jax.devices())}
    prog, _ = harness.build_step(cell, devices)
    # the launch layer's registry sets another rope_theta than the file
    out["rope_theta"] = {"run": prog.cfg.rope_theta, "file": cell.config[
        "rope_theta"], "registry": get_config("qwen2-0.5b").rope_theta}
    text = prog.compiled.as_text()
    out["all_reduces"] = text.count(" all-reduce(") + \
        text.count(" all-reduce-start(")
    state, _, got = harness.check_rounds(prog, cell, SEED)
    # one client a chip: each device holds its client's slice of the state
    leaf = max(jax.tree.leaves(state["params"]), key=lambda a: a.size)
    out["client_shards"] = sorted(
        (s.index[0].start or 0, s.device.id) for s in leaf.addressable_shards)
    del state, prog
    want = harness.reference_side(cell, devices, SEED)
    out["numbers"] = check.numbers(got, want)
    # the control: the program's own bfloat16 path, at 4 layers and 128
    # tokens, as test_check.py runs it on one chip
    big = tiny.cell("qwen2", seq_len=128, layers=4, clients=4)
    prog, _ = harness.build_step(big, devices, dtype="bfloat16")
    _, _, got = harness.check_rounds(prog, big, SEED)
    del prog
    nums = check.numbers(got, harness.reference_side(big, devices, SEED))
    out["control_passes"] = check.verdict(nums,
                                          big.workload["check"]["limits"])
    for fault in (None, "no_exchange", "half_batch", "frozen"):
        with faults.FAULTS[fault]() if fault else contextlib.nullcontext():
            res = harness.run_cell(cell, SEED, 0.3, False, devices,
                                   time.time(), {"bf16_flops_per_s": 197e12})
        out[fault or "sound"] = {k: res[k] for k in
                                 ("correct", "failed", "checks")}
        out[fault or "sound"]["count"] = res["device"]["count"]
    print(json.dumps(out, default=lambda x: float(np.asarray(x))))


if __name__ == "__main__":
    main()
