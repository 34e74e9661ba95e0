"""Device time under the program's named scopes, from a traced window.

The round step names its layers with ``jax.named_scope`` (``model``,
``local_step``, ``sync``, ``server`` in ``core/engine.py``; ``attention``,
``lm_head`` in ``models/layers.py``; ``ssd_scan`` in ``models/ssm.py``).
A scope survives compilation only in the HLO metadata: every instruction of
the compiled module, fusions included, carries ``metadata={op_name="..."}``,
the '/'-joined name stack of the operation it came from (a fusion carries
its root's). A device operation in the trace is named by its instruction,
so the compiled text maps it to its scopes. The profiler's own reader does
not hand the metadata on, hence the text.

The harness frees its compiled step before the readers run, so the first
reader of a run compiles the step of the cell this process runs again, by
the harness's own ``build_step`` (``run.py``'s persistent cache makes that
a load), and keeps the map on the readers' shared ``ctx`` for the others.
Only that function imports the program, and only when called.
"""
from __future__ import annotations

import argparse
import os
import re
import sys

from benchmarks.chip import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# '%fused_computation.3 (param_0: f32[8]) -> f32[8] {', 'ENTRY %main.9 ...{'
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
# '  ROOT %fusion.12 = f32[8]{0} fusion(...), ..., metadata={op_name="..."}'
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
# 'transpose(jvp(attention))' -> 'jvp(attention)' -> 'attention'
_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")


def op_scopes(hlo_text: str) -> dict:
    """{instruction name: op_name} over every computation of a compiled
    module's text. An instruction without an op_name of its own that calls
    computations (a fusion whose root a pass of XLA made, such as a dot
    rewritten as a convolution) takes the deepest op_name, the one of most
    elements, found in them; '' where there is none."""
    own, calls, body = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            if c:
                comp = c.group(1)
                body[comp] = []
            continue
        inst = m.group(1)
        name = _OP_NAME.search(line)
        own[inst] = name.group(1) if name else ""
        calls[inst] = _CALLS.findall(line)
        if comp is not None:
            body[comp].append(inst)
    deepest = {}

    def resolved(inst):
        if own[inst] or not calls[inst]:
            return own[inst]
        return max((inherited(c) for c in calls[inst]), key=_depth)

    def inherited(comp):
        if comp not in deepest:
            deepest[comp] = ""          # a cycle reads as nothing
            deepest[comp] = max((resolved(i) for i in body.get(comp, ())),
                                key=_depth, default="")
        return deepest[comp]

    return {inst: resolved(inst) for inst in own}


def _depth(op_name: str) -> int:
    return op_name.count("/") + 1 if op_name else 0


def _unwrapped(element: str) -> str:
    while True:
        m = _WRAPPED.match(element)
        if not m:
            return element
        element = m.group(1)


def in_scope(op_name: str, scope: str) -> bool:
    """True when some '/'-separated element of ``op_name``, its transform
    wrappers (``jvp(...)``, ``transpose(...)``, ``vmap(...)``) stripped,
    is ``scope``."""
    return any(_unwrapped(e) == scope for e in op_name.split("/"))


def scope_ms(ev: trace.Events, scopes: dict, scope: str):
    """Device ms a round under ``scope``: the operations of the traced
    window whose instruction is in the scope, clipped to the window, summed,
    averaged over the chips and divided by the rounds traced (the
    ``dispatch`` spans that start in the window). A loop or call, whose
    event holds the operations it runs, is left out so that no time counts
    twice. None when no instruction of the program is in the scope, or no
    round started in the window."""
    inside = {n for n, op in scopes.items() if in_scope(op, scope)}
    lo, hi = ev.window
    rounds = trace.rounds(ev)
    if not inside or not rounds:
        return None
    total = 0.0
    for ops in ev.device_ops:
        for s, e, name in ops:
            s, e = max(s, lo), min(e, hi)
            n = trace.op_name(name)
            if e > s and n in inside and not trace.CONTAINER.match(n):
                total += e - s
    return 1e3 * total / len(ev.device_ops) / rounds


def running_cell_scopes() -> dict:
    """``op_scopes`` of the compiled round step of the cell that this
    process runs, named by ``run.py``'s ``--workload`` on its command line;
    {} when there is none."""
    ap = argparse.ArgumentParser(add_help=False, allow_abbrev=False)
    ap.add_argument("--workload")
    name = ap.parse_known_args(sys.argv[1:])[0].workload
    if name is None:
        return {}
    import jax

    from benchmarks.chip import harness, spec
    cell = spec.load_cell(ROOT, name)
    prog, _ = harness.build_step(cell, jax.devices()[:cell.chips])
    return op_scopes(prog.compiled.as_text())


def read(ctx, scope: str):
    """A reader's value: ``scope_ms`` of ``ctx.events`` under ``scope``,
    the instructions' op_names from ``ctx.scopes``, which the first reader
    of a run sets from ``running_cell_scopes``. None without a trace, or
    where the program names no instruction in the scope."""
    if ctx.events is None:
        return None
    if getattr(ctx, "scopes", None) is None:
        ctx.scopes = running_cell_scopes()
    return scope_ms(ctx.events, ctx.scopes, scope)
