"""The check that decides ``correct``, driven as a run drives it (the
harness's look for a chip skipped) on CPU-sized cells: the program agrees
with the plain reference, and each fault a training cell can have, planted
under the timed path, comes out not correct against the cell's limits."""
import contextlib
import time

import jax
import pytest

from benchmarks.chip import check, faults, harness
from benchmarks.chip.tests import tiny

SEED = 2 ** 31 + 17


def _run(kind, fault=None, devices=None):
    ctx = faults.FAULTS[fault]() if fault else contextlib.nullcontext()
    with ctx:
        return harness.run_cell(tiny.cell(kind), SEED, 0.3, False,
                                devices or jax.devices()[:1], time.time(),
                                {"bf16_flops_per_s": 197e12})


@pytest.mark.parametrize("kind", ["qwen2", "mamba2"])
def test_program_agrees_with_the_reference(kind):
    prog, _ = harness.build_step(tiny.cell(kind), jax.devices()[:1])
    _, _, got = harness.check_rounds(prog, tiny.cell(kind), SEED)
    want = harness.reference_side(tiny.cell(kind), jax.devices()[:1], SEED)
    nums = check.numbers(got, want)
    # both sides in float32 on the CPU: rounding apart
    assert all(nums[n] < 1e-5 for n in check.NAMES), nums


@pytest.mark.parametrize("kind", ["qwen2", "mamba2"])
def test_a_sound_run_is_correct(kind):
    res = _run(kind)
    assert res["correct"] is True and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) >= {"setup_s", "train_tokens_per_s"}


@pytest.mark.parametrize("fault", ["frozen", "half_batch"])
@pytest.mark.parametrize("kind", ["qwen2", "mamba2"])
def test_a_planted_fault_is_not_correct(kind, fault):
    res = _run(kind, fault)
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("kind", ["qwen2", "mamba2"])
def test_the_control_is_not_correct(kind):
    """The control, the program's own bfloat16 compute path (the precision
    below the configuration's float32), fails the cell's limits; at 4
    layers and 128 tokens, since fewer roundings err less."""
    cell = tiny.cell(kind, seq_len=128, layers=4)
    devs = jax.devices()[:1]
    prog, _ = harness.build_step(cell, devs, dtype="bfloat16")
    _, _, got = harness.check_rounds(prog, cell, SEED)
    ref = harness.reference_side(cell, devs, SEED)
    nums = check.numbers(got, ref)
    assert not check.verdict(nums, cell.workload["check"]["limits"]), nums
