"""From a profiler trace (``.xplane.pb``) to device busy time and the
run's ``breakdown``.

The harness traces a few rounds after its measured window, inside a host
span ``bench_trace``, with a host span around each part of a round
(``make_batch``, ``put_batch``, ``dispatch``, ``read_loss``). Each chip's
plane (``/device:TPU:<id>``) has a line ``XLA Ops`` with one event per
operation run on it. Its busy time is the union of those events' intervals
inside the traced window (a loop's event covers the operations it runs);
an idle gap is an interval of the window that no operation covers, named
by the host span that overlaps it most. A collective (an all-reduce,
all-gather, reduce-scatter, collective-permute or all-to-all, or the
interval from the start of an asynchronous one to its done) is exposed
where no other operation runs on that chip.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

HOST_SPANS = ("make_batch", "put_batch", "dispatch", "read_loss")
WINDOW_SPAN = "bench_trace"
OPS_LINE = "XLA Ops"
# operations whose event spans the operations they run (a scan's loop)
CONTAINER = re.compile(r"^(while|conditional|call)\b")
# a collective, by its HLO opcode ('%ar.3 = f32[8]{0} all-reduce(...') or,
# where the event carries no text, by its name ('all-reduce-start.3')
_KINDS = r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
_COLLECTIVE_OP = re.compile(rf"\s({_KINDS})(-start|-done)?\(")
_COLLECTIVE_NAME = re.compile(rf"^({_KINDS})(-start|-done)?(?:[.\-]|$)")
# the first operand of an asynchronous done: the start it waits for
_OPERAND = re.compile(r"-done\([^%]*%([\w.\-]+)")


@dataclasses.dataclass
class Events:
    """Intervals in seconds on one clock: the traced window, each chip's
    operations (start, end, name), and the host spans (start, end, name)."""
    window: tuple
    device_ops: list
    host_spans: list


def find_xplane(logdir: str) -> str:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} .xplane.pb files under "
                                f"{logdir}")
    return found[0]


def op_name(event_name: str) -> str:
    """'%fusion.12 = f32[8]{0} fusion(...)' -> 'fusion.12'."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def host_events(pd, names) -> list:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9, e.name))
    return sorted(out)


def from_profile(pd, device_ids, device_prefix="/device:TPU:") -> Events:
    """The events of the chips ``device_ids`` and of the harness's host
    spans, from a ``jax.profiler.ProfileData``."""
    spans = host_events(pd, (WINDOW_SPAN,) + HOST_SPANS)
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} '{WINDOW_SPAN}' spans in the trace")
    planes = {p.name: p for p in pd.planes}
    ops = []
    for i in device_ids:
        plane = planes.get(f"{device_prefix}{i}")
        if plane is None:
            raise ValueError(f"no plane {device_prefix}{i} in the trace; "
                             f"planes: {sorted(planes)}")
        evs = [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9,
                e.name) for ln in plane.lines if ln.name == OPS_LINE
               for e in ln.events]
        ops.append(sorted(evs))
    return Events(window=windows[0], device_ops=ops,
                  host_spans=[s for s in spans if s[2] != WINDOW_SPAN])


def union(intervals, lo, hi) -> list:
    """Merged (start, end) of ``intervals`` clipped to [lo, hi]."""
    out = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def window_s(ev: Events) -> float:
    return ev.window[1] - ev.window[0]


def busy_s(ev: Events) -> list:
    """Seconds of the window in which an operation ran, per chip."""
    lo, hi = ev.window
    return [length(union(ops, lo, hi)) for ops in ev.device_ops]


def overlap(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def rounds(ev: Events) -> int:
    """Rounds traced: the ``dispatch`` spans that start in the window."""
    lo, hi = ev.window
    return sum(name == "dispatch" and lo <= s < hi
               for s, _, name in ev.host_spans)


def collective(event_name: str):
    """(kind, '' | '-start' | '-done') of a collective operation's event,
    None for any other."""
    text = event_name.split(" = ", 1)
    m = _COLLECTIVE_OP.search(" " + text[1]) if len(text) == 2 else \
        _COLLECTIVE_NAME.match(op_name(event_name))
    return (m.group(1), m.group(2) or "") if m else None


def exposed_collective_s(ev: Events) -> list:
    """Per chip, seconds of the window in which a collective was under
    way and no other operation ran: the union of the collectives'
    intervals (an asynchronous one from its start to its done), less
    what the other operations cover. A loop or call, whose event holds
    the operations it runs, is no other operation. None for a chip with
    no collective in the window."""
    lo, hi = ev.window
    out = []
    for ops in ev.device_ops:
        coll, other, started = [], [], {}
        for s, e, name in ops:
            kind = collective(name)
            n = op_name(name)
            if kind is None:
                if not CONTAINER.match(n):
                    other.append((s, e))
                continue
            coll.append((s, e))
            if kind[1] == "-start":
                started.setdefault(kind[0], {})[n] = s
            elif kind[1] == "-done":
                waits = started.get(kind[0], {})
                m = _OPERAND.search(name)
                begin = waits.pop(m.group(1), None) if m else None
                if begin is None and waits:       # no operand: the latest
                    begin = waits.pop(list(waits)[-1])
                if begin is not None:
                    coll.append((begin, e))
        merged = union(coll, lo, hi)
        out.append(length(merged) - overlap(merged, union(other, lo, hi))
                   if merged else None)
    return out


def top_device_ops(ev: Events, k: int = 10) -> list:
    """[[operation, seconds]]: the operations that took most device time
    in the window, summed over its events and averaged over the chips.
    A loop or call, whose event holds the operations it runs, is left out
    so that no time counts twice."""
    lo, hi = ev.window
    tot = collections.Counter()
    for ops in ev.device_ops:
        for s, e, name in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s and not CONTAINER.match(op_name(name)):
                tot[op_name(name)] += e - s
    n = len(ev.device_ops)
    return [[name, t / n] for name, t in tot.most_common(k)]


def idle_gaps(ev: Events, k: int = 10, chip: int = 0) -> list:
    """[[host span, seconds]]: the longest idle gaps of one chip in the
    window, each named by the host span that overlaps it most."""
    lo, hi = ev.window
    busy = union(ev.device_ops[chip], lo, hi)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    named = []
    for gs, ge in gaps:
        best, over = "no host span", 0.0
        for s, e, name in ev.host_spans:
            o = min(e, ge) - max(s, gs)
            if o > over:
                best, over = name, o
        named.append([best, ge - gs])
    named.sort(key=lambda x: -x[1])
    return named[:k]
