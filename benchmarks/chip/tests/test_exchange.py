"""The cross-client average is named ``exchange`` in the compiled round
step: on four chips, one client a chip, every all-reduce that moves a
parameter-sized tree carries the scope, so ``exchange_ms.train`` reads the
exchange across chips; on one chip every instruction under it also lies
under ``sync`` or ``server``, so the scopes the one-chip cells read hold
what they held before."""
import json
import os
import subprocess
import sys

import jax
import pytest

from benchmarks.chip import harness, scopes
from benchmarks.chip.tests import tiny
from benchmarks.chip.tests.tiny import ROOT


@pytest.fixture(scope="module")
def mesh_all_reduces():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [ROOT, os.path.join(ROOT, "src"),
                    os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.chip.tests.exchange_worker"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_every_parameter_sized_all_reduce_is_the_exchange(mesh_all_reduces):
    smallest = mesh_all_reduces["smallest_leaf"]
    large = [a for a in mesh_all_reduces["all_reduces"] if a[1] >= smallest]
    # the client drift's, params' and momentum's means in the sync, the
    # last gradients' in the server (XLA may combine them into one)
    assert large, mesh_all_reduces
    for name, _, op_name in large:
        assert scopes.in_scope(op_name, "exchange"), (name, op_name)


def test_on_one_chip_the_exchange_lies_in_sync_or_server():
    prog, _ = harness.build_step(tiny.cell("qwen2"), jax.devices()[:1])
    ops = scopes.op_scopes(prog.compiled.as_text())
    inside = [op for op in ops.values() if scopes.in_scope(op, "exchange")]
    assert inside
    for op in inside:
        assert scopes.in_scope(op, "sync") or scopes.in_scope(op, "server"), op
