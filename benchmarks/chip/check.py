"""The comparison that decides ``correct``: the program's first rounds
against the plain reference's, on the same seeded weights and batches.

Five numbers, each compared against its own limit from the cell's
workload file where the file gives one (a number with no upper reading has
none, and is not compared):

* ``loss_gap`` — the largest |loss_program - loss_reference| over the
  rounds compared, in nats (a round's loss is the mean over its M x H
  local steps);
* ``grad_gap`` — over the parameter leaves, the largest gap between the
  norms of the first round's client-averaged gradient, program against
  reference, over the larger of the reference leaf's norm and the median
  leaf's;
* ``change_gap`` — the same for the norm of each leaf's change from the
  start after the rounds, over the leaves that count: a leaf whose
  reference gradient is below a thousandth of the median leaf's (a key's
  bias under softmax) moves by round-off alone and is left out;
* ``grad_err``, ``change_err`` — the same two, with the norm of the
  elementwise difference (|g| of the program against the reference's, and
  the change) in place of the gap of the norms. A leaf of more than
  ``harness.SAMPLE`` elements is compared on that many drawn from the seed,
  the same on both sides, and the norm of the difference scaled up to the
  leaf's size. A gap of norms reads the program's bfloat16 path like its
  float32 one; these read it at about twice the float32 path's.
"""
from __future__ import annotations

import math
import statistics

import numpy as np

NEGLIGIBLE_GRAD = 1e-3      # of the median leaf's gradient norm
NAMES = ("loss_gap", "grad_gap", "change_gap", "grad_err", "change_err")


def _diff_norm(a, b, size: int) -> float:
    """||a - b|| over a leaf of ``size`` elements from samples a, b."""
    d = np.asarray(a, np.float64) - np.asarray(b, np.float64)
    return float(np.linalg.norm(d)) * math.sqrt(size / d.size)


def leaf_gaps(prog: dict, ref: dict) -> dict:
    """Each leaf's gaps, over the larger of its reference norm and the
    median leaf's (``None`` for a leaf that does not count): what the
    worst-leaf numbers take the largest of."""
    gmed = statistics.median(ref["grad"])
    counts = [g >= NEGLIGIBLE_GRAD * gmed for g in ref["grad"]]
    cmed = statistics.median([r for r, k in zip(ref["change"], counts) if k])
    gden = [max(r, gmed) for r in ref["grad"]]
    cden = [max(r, cmed) if k else None
            for r, k in zip(ref["change"], counts)]
    sizes = ref["sizes"]
    return {
        "grad": [abs(p - r) / d
                 for p, r, d in zip(prog["grad"], ref["grad"], gden)],
        "change": [abs(p - r) / d if d else None
                   for p, r, d in zip(prog["change"], ref["change"], cden)],
        "grad_err": [_diff_norm(p, r, n) / d for p, r, n, d in zip(
            prog["grad_sample"], ref["grad_sample"], sizes, gden)],
        "change_err": [_diff_norm(p, r, n) / d if d else None
                       for p, r, n, d in zip(prog["change_sample"],
                                             ref["change_sample"], sizes,
                                             cden)],
    }


def numbers(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"losses", "grad", "change", "grad_sample",
    "change_sample", "sizes"}, leaf lists in the same (tree-flattening)
    order."""
    keys = ("grad", "change", "grad_sample", "change_sample")
    if len(prog["losses"]) != len(ref["losses"]) or len(
            {len(d[k]) for d in (prog, ref) for k in keys}
            | {len(ref["sizes"])}) != 1:
        raise ValueError("program and reference readings do not line up")
    vals = prog["losses"] + prog["grad"] + prog["change"]
    if not (all(math.isfinite(v) for v in vals) and all(
            np.isfinite(a).all() for k in ("grad_sample", "change_sample")
            for a in prog[k])):
        return {n: math.inf for n in NAMES}
    gaps = leaf_gaps(prog, ref)
    worst = lambda xs: max(x for x in xs if x is not None)
    return {
        "loss_gap": max(abs(p - r) for p, r in zip(prog["losses"],
                                                    ref["losses"])),
        "grad_gap": worst(gaps["grad"]),
        "change_gap": worst(gaps["change"]),
        "grad_err": worst(gaps["grad_err"]),
        "change_err": worst(gaps["change_err"]),
    }


def compared(limits: dict) -> list:
    """The numbers a cell compares: those its limits name."""
    unknown = set(limits) - set(NAMES)
    if unknown or not limits:
        raise ValueError(f"limits name {sorted(unknown) or 'no number'}")
    return [n for n in NAMES if n in limits]


def verdict(nums: dict, limits: dict) -> bool:
    return all(nums[n] <= limits[n] for n in compared(limits))


def lines(nums: dict, limits: dict) -> list:
    return [f"check {n} {nums[n]!r} limit {limits[n]!r}"
            for n in compared(limits)]


def as_json(nums: dict, limits: dict) -> dict:
    return {n: {"value": nums[n], "limit": limits[n]}
            for n in compared(limits)}
