"""Compiles the qwen2 cell's round step at the tiny size with four clients
on four virtual CPU devices, one client a chip (the launch layer's mesh
step), and prints, as one JSON line, each all-reduce of the compiled
program with its largest operand's elements and its op_name, beside the
elements of the smallest parameter leaf. ``test_exchange.py`` starts it in
a process of its own, with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``
set before JAX starts."""
import json
import math
import re

import jax

from benchmarks.chip import harness, program, scopes
from benchmarks.chip.tests import tiny

# '  %all-reduce.3 = (f32[2,8]{1,0}, f32[8]{0}) all-reduce(...), ...'
_ALL_REDUCE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*?)\s+all-reduce(?:-start)?\(")
_ARRAY = re.compile(r"\w+\[([\d,]*)\]")


def all_reduces(text: str) -> list:
    """[instruction name, elements of its largest operand, op_name] of
    each all-reduce (or its asynchronous start) in compiled HLO text."""
    ops = scopes.op_scopes(text)
    out = []
    for line in text.splitlines():
        m = _ALL_REDUCE.match(line)
        if m:
            sizes = [math.prod(int(d) for d in dims.split(",") if d)
                     for dims in _ARRAY.findall(m.group(2))]
            out.append([m.group(1), max(sizes), ops[m.group(1)]])
    return out


def main():
    cell = tiny.cell("qwen2", clients=4)
    prog, _ = harness.build_step(cell, jax.devices()[:4])
    leaves = jax.tree.leaves(program.param_shapes(prog.cfg))
    print(json.dumps({
        "smallest_leaf": min(math.prod(a.shape) for a in leaves),
        "all_reduces": all_reduces(prog.compiled.as_text())}))


if __name__ == "__main__":
    main()
