"""The round step on four chips (the launch layer's mesh step, one client
a chip), at the tiny size of the qwen2 cell with four clients, driven as
a run drives it on four virtual CPU devices in a process of its own: it
runs the configuration file's model, its first rounds agree with the
plain reference, a sound run is correct, and the control (the program's
bfloat16 path) and the faults a four-chip cell can have (the exchange
between the chips left out, half of each batch left out, the state
returned unchanged) come out not correct."""
import json
import os
import subprocess
import sys

import pytest

from benchmarks.chip import check
from benchmarks.chip.tests.tiny import ROOT


@pytest.fixture(scope="module")
def mesh_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [ROOT, os.path.join(ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.chip.tests.mesh_worker"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_step_runs_one_client_a_chip(mesh_run):
    assert mesh_run["devices"] == 4
    # client c's slice of the state is on device c
    assert mesh_run["client_shards"] == [[c, c] for c in range(4)]
    assert mesh_run["all_reduces"] > 0


def test_the_mesh_step_runs_the_files_configuration(mesh_run):
    # the launch layer builds its step from its registry by name; the
    # benchmark hands it the file's configuration, and the build checks
    # that the step's ``meta["cfg"]`` is that one
    theta = mesh_run["rope_theta"]
    assert theta["run"] == theta["file"] != theta["registry"], theta


def test_the_mesh_step_agrees_with_the_reference(mesh_run):
    nums = mesh_run["numbers"]
    # both sides in float32 on the CPU: rounding apart
    assert all(nums[n] < 1e-5 for n in check.NAMES), nums


def test_a_sound_run_on_four_chips_is_correct(mesh_run):
    res = mesh_run["sound"]
    assert res["correct"] is True and res["failed"] == 0, res
    assert res["count"] == 4


@pytest.mark.parametrize("fault", ["no_exchange", "half_batch", "frozen"])
def test_a_planted_fault_on_four_chips_is_not_correct(mesh_run, fault):
    assert mesh_run[fault]["correct"] is False, mesh_run[fault]["checks"]


def test_the_control_on_four_chips_is_not_correct(mesh_run):
    assert mesh_run["control_passes"] is False
