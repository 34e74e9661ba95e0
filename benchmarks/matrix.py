"""Declarative benchmark-matrix harness (benchalot-style) — the single
contract every bench in this repo emits through (DESIGN.md §11).

A bench is a ``MatrixConfig`` (named axes × per-bench fixed params ×
samples/seed policy) plus a ``run(point, ctx) -> rows`` callable registered
in ``REGISTRY`` (benchmarks/run.py registers all of them at import).  One
runner expands the matrix deterministically, tags every row with its full
axis coordinates + ``git_rev`` + schema version, asserts the uniform row
schema at emit time, and writes both artifacts:

  BENCH_<name>.json        (repo root)     — the store of record
  results/bench/<name>.csv (derived)       — byte-identical function of the
                                             JSON rows; regenerable without
                                             re-running via ``update-output``

Uniform BENCH document, schema v1::

  {"schema_version": 1, "bench": "<name>", "git_rev": "<rev of the run>",
   "config": {...fixed params + runtime context...},
   "axes": ["method", "arm", ...],            # ordered coord keys
   "rows": [{"coords": {axis: scalar, ...},   # exactly the doc's axes
             "metrics": {name: number, ...},  # numeric only, never bool/NaN
             "info": {...},                   # optional non-numeric payload
             "git_rev": "<rev>"}, ...]}       # required per row

``benchmarks/diff.py`` joins two documents on the coordinate tuples and
prints per-metric deltas, so a cross-PR regression is a single diff.

CLI (``python -m benchmarks.matrix <cmd>``)::

  run --bench NAME [--select axis=v1,v2]... [--limit N] [--set k=v]...
      [--out-dir D] [--results-dir D]      expand + run + validate + emit
  update-output [--bench NAME | PATH...]   regenerate CSV/summary from the
                                           stored JSON without re-running
  validate PATH...                         schema-check BENCH documents
  expand --bench NAME                      print the deterministic points
  migrate [--write]                        one-shot legacy-artifact converter
"""
from __future__ import annotations

import dataclasses
import itertools
import json
import math
import os
import re
import sys

SCHEMA_VERSION = 1

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS_DIR = os.path.join(_REPO_ROOT, "results", "bench")

_GIT_REV = None


def git_rev():
    """Short git rev of the tree the numbers came from (benchmark hygiene:
    every emitted row is attributable to a commit). Cached; "unknown"
    outside a git checkout."""
    global _GIT_REV
    if _GIT_REV is None:
        import subprocess
        try:
            _GIT_REV = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                cwd=os.path.dirname(os.path.abspath(__file__)),
                capture_output=True, text=True, timeout=10,
            ).stdout.strip() or "unknown"
        except Exception:
            _GIT_REV = "unknown"
    return _GIT_REV


# --------------------------------------------------------------------------- #
# timing classification — which metrics are wall-clock noise, not regressions
# --------------------------------------------------------------------------- #

# Wall-clock metrics vary run-to-run on the same rev; diff.py reports them
# separately and never counts them as regressions.  Everything simulated
# (sim_*: the lognormal-trace clock is a spec constant), counted (rounds,
# bytes, launches) or converged (losses at fixed seeds) is comparable.
_TIMING_PATTERNS = (
    r"(^|_)ms(_|$)",            # round_ms_mean, us->ms families
    r"(^|_)us(_|$)",            # us_fused_oracle, kernel µs/call
    r"(^|_)wall",               # wall_tok_per_s, round_wall_s_mean
    r"(^|_)tok(ens)?_per_s($|_)",  # wall-derived throughput
    r"(^|_)s$",                 # ttft_s, decode_s, p99_token_s, compile_s
    r"^seconds$",
)
_TIMING_RE = re.compile("|".join(_TIMING_PATTERNS))


def is_timing_metric(name):
    """True when ``name`` is a wall-clock measurement (noise across runs of
    the same rev).  ``sim_*`` metrics are deterministic simulated clocks and
    are always comparable, whatever their suffix."""
    if name.startswith("sim_"):
        return False
    return bool(_TIMING_RE.search(name))


# --------------------------------------------------------------------------- #
# config model
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class MatrixConfig:
    """Declarative bench matrix: ordered axes (name -> value tuple), fixed
    per-bench params, and the samples/seed policy.  Expansion is a pure
    function of this object: same config -> identical point order."""
    name: str
    axes: tuple            # ((axis, (v1, v2, ...)), ...) — ordered
    fixed: tuple = ()      # ((key, value), ...) — per-bench fixed params
    row_axes: tuple = ()   # extra per-row coord keys the runner emits
                           # (e.g. "round" for per-round curves)
    samples: int = 1       # repeats per point; seeds seed0..seed0+samples-1
    seed0: int = 0

    @classmethod
    def make(cls, name, axes, fixed=None, row_axes=(), samples=1, seed0=0):
        return cls(name=name,
                   axes=tuple((a, tuple(vs)) for a, vs in dict(axes).items()),
                   fixed=tuple(dict(fixed or {}).items()),
                   row_axes=tuple(row_axes), samples=samples, seed0=seed0)

    def axes_dict(self):
        return dict(self.axes)

    def fixed_dict(self):
        return dict(self.fixed)

    def coord_keys(self):
        ks = [a for a, _ in self.axes]
        if self.samples > 1:
            ks.append("sample")
        return ks + list(self.row_axes)


@dataclasses.dataclass(frozen=True)
class Point:
    """One matrix point: its axis coordinates, the resolved fixed params
    (config fixed <- overrides, in that precedence), and its seed."""
    coords: dict
    fixed: dict
    seed: int


def expand(cfg, select=None, limit=None, overrides=None):
    """Deterministic matrix expansion: itertools.product in declared axis
    order (last axis fastest), samples innermost.  ``select`` subsets axis
    values ({axis: (v, ...)}), ``limit`` truncates to the first N points,
    ``overrides`` wins over cfg.fixed (fixed-param precedence: CLI --set >
    MatrixConfig.fixed)."""
    select = dict(select or {})
    for ax in select:
        if ax not in cfg.axes_dict():
            raise KeyError(f"--select axis {ax!r} not in matrix "
                           f"{sorted(cfg.axes_dict())}")
    names, domains = [], []
    for axis, values in cfg.axes:
        keep = select.get(axis)
        vals = tuple(v for v in values if keep is None or v in keep)
        if not vals:
            raise ValueError(f"selection emptied axis {axis!r}")
        names.append(axis)
        domains.append(vals)
    fixed = {**cfg.fixed_dict(), **dict(overrides or {})}
    points = []
    for combo in itertools.product(*domains):
        for s in range(cfg.samples):
            coords = dict(zip(names, combo))
            if cfg.samples > 1:
                coords["sample"] = s
            points.append(Point(coords=coords, fixed=dict(fixed),
                                seed=cfg.seed0 + s))
    if limit is not None:
        points = points[:limit]
    return points


# --------------------------------------------------------------------------- #
# rows and schema
# --------------------------------------------------------------------------- #


def _scalarize(v):
    """Coerce numpy scalars to python; leave everything else alone."""
    if hasattr(v, "item") and not isinstance(v, (str, bytes)):
        try:
            return v.item()
        except Exception:
            return v
    return v


def _is_number(v):
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def make_row(coords, values, info=None, rev=None):
    """Build a schema-v1 row.  ``values`` is partitioned automatically:
    numeric (non-bool) entries become metrics, everything else joins
    ``info`` (loss curves, knob trajectories, tune dicts ...)."""
    metrics, extra = {}, dict(info or {})
    for k, v in values.items():
        v = _scalarize(v)
        if _is_number(v):
            metrics[k] = v
        else:
            extra[k] = v
    row = {"coords": {k: _scalarize(v) for k, v in coords.items()},
           "metrics": metrics,
           "git_rev": rev or git_rev()}
    if extra:
        row["info"] = extra
    return row


def validate_doc(doc):
    """The uniform-row schema validator (importable: the runner asserts it
    at emit time, tests/test_bench_schema.py runs it over every committed
    artifact).  Returns a list of error strings; empty means valid."""
    errs = []
    if not isinstance(doc, dict):
        return ["document is not a JSON object"]

    def _req(key, typ, name=None):
        v = doc.get(key)
        if not isinstance(v, typ) or (typ is str and not v):
            errs.append(f"missing/invalid {name or key!r}")
            return None
        return v

    if doc.get("schema_version") != SCHEMA_VERSION:
        errs.append(f"schema_version must be {SCHEMA_VERSION}, "
                    f"got {doc.get('schema_version')!r}")
    _req("bench", str)
    _req("git_rev", str, "document git_rev")
    _req("config", dict)
    axes = _req("axes", list)
    rows = _req("rows", list)
    if axes is not None:
        if not axes or len(set(axes)) != len(axes) \
                or not all(isinstance(a, str) and a for a in axes):
            errs.append("axes must be a non-empty list of unique names")
    if errs or rows is None or axes is None:
        return errs
    seen = {}
    for i, row in enumerate(rows):
        where = f"rows[{i}]"
        if not isinstance(row, dict):
            errs.append(f"{where}: not an object")
            continue
        coords = row.get("coords")
        if not isinstance(coords, dict) or set(coords) != set(axes):
            errs.append(f"{where}: coords keys {sorted(coords or {})} != "
                        f"axes {sorted(axes)} (coordinate completeness)")
        else:
            for a, v in coords.items():
                if v is None or not isinstance(v, (str, bool, int, float)):
                    errs.append(f"{where}: coord {a!r} is not a scalar")
            key = tuple(str(coords[a]) for a in axes)
            if key in seen:
                errs.append(f"{where}: duplicate coordinates {key} "
                            f"(first at rows[{seen[key]}])")
            seen.setdefault(key, i)
        metrics = row.get("metrics")
        if not isinstance(metrics, dict) or not metrics:
            errs.append(f"{where}: metrics must be a non-empty object")
        else:
            for m, v in metrics.items():
                if not _is_number(v):
                    errs.append(f"{where}: metric {m!r} is not numeric "
                                f"(got {type(v).__name__})")
                elif isinstance(v, float) and math.isnan(v):
                    errs.append(f"{where}: metric {m!r} is NaN")
        rev = row.get("git_rev")
        if not isinstance(rev, str) or not rev:
            errs.append(f"{where}: missing git_rev tag")
        if "info" in row and not isinstance(row["info"], dict):
            errs.append(f"{where}: info must be an object")
        unknown = set(row) - {"coords", "metrics", "info", "git_rev"}
        if unknown:
            errs.append(f"{where}: unknown keys {sorted(unknown)}")
    return errs


def assert_valid(doc):
    errs = validate_doc(doc)
    if errs:
        raise ValueError(
            f"BENCH_{doc.get('bench', '?')} fails schema v{SCHEMA_VERSION}:\n"
            + "\n".join("  - " + e for e in errs))
    return doc


# --------------------------------------------------------------------------- #
# CSV rendering — a pure, byte-deterministic function of the JSON document
# --------------------------------------------------------------------------- #


def _cell(v):
    return "" if v is None else str(v)


def render_csv(doc):
    """Columns: axes (declared order), then metric names in first-seen row
    order, then git_rev.  Missing metrics render as empty cells.  Pure
    function of the document — ``update-output`` regenerates the CSV
    byte-identically from the stored JSON."""
    axes = list(doc["axes"])
    metric_cols = []
    for row in doc["rows"]:
        for m in row["metrics"]:
            if m not in metric_cols:
                metric_cols.append(m)
    lines = [",".join(axes + metric_cols + ["git_rev"])]
    for row in doc["rows"]:
        cells = [_cell(row["coords"].get(a)) for a in axes]
        cells += [_cell(row["metrics"].get(m)) for m in metric_cols]
        cells.append(row["git_rev"])
        lines.append(",".join(cells))
    return "\n".join(lines) + "\n"


# --------------------------------------------------------------------------- #
# registry
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class BenchDef:
    """A registered bench: its matrix + the point runner.

    run(point, ctx) -> [row, ...] — rows built with make_row; coords must be
    point.coords plus any cfg.row_axes keys.  ``ctx`` is a plain dict shared
    across the points of one run_bench call (cross-point state: cached
    datasets, the sync arm's target_loss for the controller arm, per-arch
    serve results).  Runners may stash runtime config under
    ctx["config_extra"] (merged into the document config).
    post(rows, ctx) -> [row, ...] appends derived rows after all points
    (e.g. the train_lm full-shape projection).
    summary(doc) -> [(metric, value), ...] derives the stdout trajectory
    lines from the stored rows alone (so update-output never re-runs).
    """
    name: str
    config: MatrixConfig
    run: object
    summary: object = None
    post: object = None
    note: str = ""


REGISTRY = {}


def register(bench):
    if bench.name != bench.config.name:
        raise ValueError(f"bench {bench.name!r} != config {bench.config.name!r}")
    REGISTRY[bench.name] = bench
    return bench


def _registry():
    """REGISTRY, with benchmarks/run.py (the registration module) loaded."""
    if not REGISTRY:
        from benchmarks import run as _run  # noqa: F401
    return REGISTRY


# --------------------------------------------------------------------------- #
# runner
# --------------------------------------------------------------------------- #


def bench_paths(name, out_dir=None, results_dir=None):
    out_dir = out_dir or _REPO_ROOT
    results_dir = results_dir or RESULTS_DIR
    return (os.path.join(out_dir, f"BENCH_{name}.json"),
            os.path.join(results_dir, f"{name}.csv"))


def write_outputs(doc, out_dir=None, results_dir=None):
    """Validate + write both artifacts.  The JSON is the store of record;
    the CSV is derived from it (never from in-memory rows) so a later
    ``update-output`` reproduces it byte-identically."""
    assert_valid(doc)
    json_path, csv_path = bench_paths(doc["bench"], out_dir, results_dir)
    os.makedirs(os.path.dirname(json_path), exist_ok=True)
    os.makedirs(os.path.dirname(csv_path), exist_ok=True)
    with open(json_path, "w") as f:
        json.dump(doc, f, indent=1)
    stored = json.load(open(json_path))
    with open(csv_path, "w") as f:
        f.write(render_csv(stored))
    return json_path, csv_path


def run_bench(name, select=None, limit=None, overrides=None, out_dir=None,
              results_dir=None):
    """Expand the bench's matrix, run every point through its registered
    runner, tag rows, validate, and emit BENCH_<name>.json + <name>.csv.
    Returns the document."""
    bench = _registry()[name]
    points = expand(bench.config, select=select, limit=limit,
                    overrides=overrides)
    ctx = {}
    rows = []
    rev = git_rev()
    for point in points:
        got = bench.run(point, ctx)
        for row in got:
            row["git_rev"] = rev
        rows.extend(got)
    if bench.post is not None:
        extra = bench.post(rows, ctx)
        for row in extra:
            row["git_rev"] = rev
        rows.extend(extra)
    cfg = bench.config
    doc = {
        "schema_version": SCHEMA_VERSION,
        "bench": name,
        "git_rev": rev,
        "config": {**cfg.fixed_dict(), **dict(overrides or {}),
                   "samples": cfg.samples, "seed0": cfg.seed0,
                   **ctx.get("config_extra", {}),
                   **({"note": bench.note} if bench.note else {})},
        "axes": cfg.coord_keys(),
        "rows": rows,
    }
    write_outputs(doc, out_dir=out_dir, results_dir=results_dir)
    return doc


def summarize(doc):
    """Derive the stdout (bench, metric, value) trajectory lines from a
    stored document — registry summary when available, else nothing."""
    bench = _registry().get(doc["bench"])
    if bench is None or bench.summary is None:
        return []
    return list(bench.summary(doc))


def update_output(path, results_dir=None):
    """benchalot-style --update-output: regenerate the CSV and summary from
    the stored JSON rows without invoking any runner."""
    doc = assert_valid(json.load(open(path)))
    _, csv_path = bench_paths(doc["bench"], results_dir=results_dir)
    os.makedirs(os.path.dirname(csv_path), exist_ok=True)
    with open(csv_path, "w") as f:
        f.write(render_csv(doc))
    return doc, csv_path


# --------------------------------------------------------------------------- #
# one-shot legacy migration (pre-PR-9 artifact shapes -> schema v1)
# --------------------------------------------------------------------------- #

# Historical compression tags <-> the composite "compression" axis values.
COMPRESSION_VARIANTS = {
    "none": ("none", 1.0, False),
    "topk0.1": ("topk", 0.1, False),
    "topk0.1-ef": ("topk", 0.1, True),
    "randk0.1": ("randk", 0.1, False),
    "int8": ("int8-stochastic", 1.0, False),
}


def _legacy_tag_to_variant(op, k, ef):
    for tag, (o, kk, e) in COMPRESSION_VARIANTS.items():
        if (o, kk, e) == (op, k, ef):
            return tag
    raise KeyError(f"unknown compression case {(op, k, ef)}")


def _doc(name, config, axes, rows, rev):
    return {"schema_version": SCHEMA_VERSION, "bench": name,
            "git_rev": rev or "unknown", "config": config,
            "axes": list(axes), "rows": rows}


def _read_legacy_csv(path):
    lines = open(path).read().splitlines()
    hdr = lines[0].split(",")
    return [dict(zip(hdr, ln.split(","))) for ln in lines[1:] if ln]


def migrate(root=None, write=False):
    """Convert every committed pre-PR-9 artifact to schema v1.  Rows (and
    documents) that predate the git_rev tag are backfilled with
    ``git_rev: "unknown"`` — never emitted schema-invalid.  Returns
    {bench_name: doc}; with write=True also rewrites BENCH_<name>.json +
    results/bench/<name>.csv (and removes artifacts whose rows moved:
    controller.csv folds into the async document's arm axis)."""
    root = root or _REPO_ROOT
    docs = {}

    def _load(fname):
        p = os.path.join(root, fname)
        return json.load(open(p)) if os.path.exists(p) else None

    def _rows_from_mapping(mapping, axis, rev):
        rows = []
        for key, rec in mapping.items():
            rows.append(make_row({axis: key}, rec, rev=rev or "unknown"))
        return rows

    # engine: {"methods": {method: rec}}
    legacy = _load("BENCH_engine.json")
    if legacy and "schema_version" not in legacy:
        rev = legacy.get("git_rev")
        docs["engine"] = _doc("engine", legacy.get("config", {}), ["method"],
                              _rows_from_mapping(legacy["methods"], "method",
                                                 rev), rev)

    # compression: {"entries": {"<method>__<tag>": rec}}
    legacy = _load("BENCH_compression.json")
    if legacy and "schema_version" not in legacy:
        rev = legacy.get("git_rev")
        rows = []
        for tag, rec in legacy["entries"].items():
            method, case = tag.split("__", 1)
            case = {"none": "none", "topk_k0.1": "topk0.1",
                    "topk_k0.1_ef": "topk0.1-ef", "randk_k0.1": "randk0.1",
                    "int8-stochastic": "int8"}[case]
            op, k, ef = COMPRESSION_VARIANTS[case]
            rows.append(make_row({"method": method, "compression": case}, rec,
                                 info={"op": op, "k": k, "error_feedback": ef},
                                 rev=rev or "unknown"))
        docs["compression"] = _doc("compression", legacy.get("config", {}),
                                   ["method", "compression"], rows, rev)

    # async: {"methods": {method: {arm: rec}}} (controller arm optional)
    legacy = _load("BENCH_async.json")
    if legacy and "schema_version" not in legacy:
        rev = legacy.get("git_rev")
        rows = []
        for method, arms in legacy["methods"].items():
            for arm, rec in arms.items():
                rows.append(make_row({"method": method, "arm": arm}, rec,
                                     rev=rev or "unknown"))
        docs["async"] = _doc("async", legacy.get("config", {}),
                             ["method", "arm"], rows, rev)

    # kernels: the micro rows lived only in results/bench/kernels.csv
    legacy = _load("BENCH_kernels.json")
    if legacy and "schema_version" not in legacy:
        rev = legacy.get("git_rev")
        micro = os.path.join(root, "results", "bench", "kernels.csv")
        if os.path.exists(micro):
            rows = []
            for r in _read_legacy_csv(micro):
                rows.append(make_row(
                    {"kernel": r["kernel"]},
                    {"us_interpret": float(r["us_interpret"]),
                     "us_ref_jit": float(r["us_ref_jit"])},
                    rev=r.get("git_rev") or "unknown"))
            docs["kernels"] = _doc(
                "kernels", {"backend": legacy.get("config", {}).get(
                    "backend", "cpu")}, ["kernel"], rows, rev)

    # serve: {"archs": {arch: {mode: rec}}}
    legacy = _load("BENCH_serve.json")
    if legacy and "schema_version" not in legacy:
        rev = legacy.get("git_rev")
        rows = []
        for arch, modes in legacy["archs"].items():
            for mode, rec in modes.items():
                rec = {k: v for k, v in rec.items() if k != "mode"}
                rows.append(make_row({"arch": arch, "mode": mode}, rec,
                                     rev=rev or "unknown"))
        docs["serve"] = _doc("serve", legacy.get("config", {}),
                             ["arch", "mode"], rows, rev)

    # train_lm: {"methods": {...}, "full_shape_projection": [...]}
    legacy = _load("BENCH_train_lm.json")
    if legacy and "schema_version" not in legacy:
        rev = legacy.get("git_rev")
        rows = _rows_from_mapping(legacy["methods"], "method", rev)
        for p in legacy.get("full_shape_projection", []):
            coords = {"method": f"projection:{p['shape']}@{p['mesh']}"}
            rec = {k: v for k, v in p.items()
                   if k not in ("shape", "mesh", "mode", "tag")}
            rows.append(make_row(
                coords, rec,
                info={k: p[k] for k in ("shape", "mesh", "mode", "tag")
                      if k in p},
                rev=rev or "unknown"))
        docs["train_lm"] = _doc("train_lm", legacy.get("config", {}),
                                ["method"], rows, rev)

    # fig1 / sec52: CSV-only legacy artifacts -> documents
    fig1 = os.path.join(root, "results", "bench", "fig1.csv")
    if os.path.exists(fig1):
        rows = []
        for r in _read_legacy_csv(fig1):
            rows.append(make_row(
                {"main_frac": float(r["main_frac"]), "method": r["method"],
                 "round": int(r["round"])},
                {"loss": float(r["loss"]), "test_acc": float(r["test_acc"])},
                rev=r.get("git_rev") or "unknown"))
        if rows and "schema_version" not in open(fig1).readline():
            docs["fig1"] = _doc("fig1", {"model": "mlp_cls", "clients": 10,
                                         "rounds": 25, "h_local": 6},
                                ["main_frac", "method", "round"], rows, None)
    sec52 = os.path.join(root, "results", "bench", "sec52.csv")
    if os.path.exists(sec52):
        rows = []
        for r in _read_legacy_csv(sec52):
            if "v_init" not in r:
                rows = []
                break
            rows.append(make_row(
                {"v_init": r["v_init"], "tau": float(r["tau"])},
                {"mean_step_norm": float(r["mean_step_norm"])},
                rev=r.get("git_rev") or "unknown"))
        if rows:
            docs["sec52"] = _doc("sec52", {"rounds": 5, "h_local": 5,
                                           "clients": 4, "method":
                                           "fedadagrad"},
                                 ["v_init", "tau"], rows, None)

    for doc in docs.values():
        assert_valid(doc)
    if write:
        for name, doc in docs.items():
            write_outputs(doc, out_dir=root,
                          results_dir=os.path.join(root, "results", "bench"))
        # controller rows now live on the async document's arm axis
        stale = os.path.join(root, "results", "bench", "controller.csv")
        if os.path.exists(stale) and "async" in docs:
            os.remove(stale)
    return docs


# --------------------------------------------------------------------------- #
# CLI
# --------------------------------------------------------------------------- #


def _parse_set(pairs):
    out = {}
    for p in pairs:
        k, _, v = p.partition("=")
        try:
            out[k] = json.loads(v)
        except json.JSONDecodeError:
            out[k] = v
    return out


def _parse_select(pairs):
    out = {}
    for p in pairs:
        axis, _, vs = p.partition("=")
        vals = []
        for v in vs.split(","):
            try:
                vals.append(json.loads(v))
            except json.JSONDecodeError:
                vals.append(v)
        out[axis] = tuple(vals)
    return out


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(prog="benchmarks.matrix",
                                 description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    p_run = sub.add_parser("run", help="expand + run + validate + emit")
    p_run.add_argument("--bench", required=True)
    p_run.add_argument("--select", action="append", default=[],
                       metavar="axis=v1,v2")
    p_run.add_argument("--limit", type=int, default=None)
    p_run.add_argument("--set", dest="sets", action="append", default=[],
                       metavar="key=value")
    p_run.add_argument("--out-dir", default=None)
    p_run.add_argument("--results-dir", default=None)

    p_upd = sub.add_parser("update-output",
                           help="regenerate CSV/summary from stored JSON "
                                "without re-running")
    p_upd.add_argument("paths", nargs="*")
    p_upd.add_argument("--bench", default=None)
    p_upd.add_argument("--results-dir", default=None)

    p_val = sub.add_parser("validate", help="schema-check BENCH documents")
    p_val.add_argument("paths", nargs="+")

    p_exp = sub.add_parser("expand", help="print the deterministic points")
    p_exp.add_argument("--bench", required=True)
    p_exp.add_argument("--select", action="append", default=[])
    p_exp.add_argument("--limit", type=int, default=None)

    p_mig = sub.add_parser("migrate", help="one-shot legacy converter")
    p_mig.add_argument("--write", action="store_true")
    p_mig.add_argument("--root", default=None)

    args = ap.parse_args(argv)

    if args.cmd == "run":
        doc = run_bench(args.bench, select=_parse_select(args.select),
                        limit=args.limit, overrides=_parse_set(args.sets),
                        out_dir=args.out_dir, results_dir=args.results_dir)
        for metric, value in summarize(doc):
            print(f"{doc['bench']},{metric},{value}")
        print(f"# {len(doc['rows'])} rows -> "
              f"{bench_paths(doc['bench'], args.out_dir, args.results_dir)[0]}")
        return 0

    if args.cmd == "update-output":
        paths = list(args.paths)
        if args.bench:
            paths.append(bench_paths(args.bench)[0])
        for path in paths:
            doc, csv_path = update_output(path, results_dir=args.results_dir)
            for metric, value in summarize(doc):
                print(f"{doc['bench']},{metric},{value}")
            print(f"# regenerated {csv_path} from {path} (no rerun)")
        return 0

    if args.cmd == "validate":
        bad = 0
        for path in args.paths:
            errs = validate_doc(json.load(open(path)))
            if errs:
                bad += 1
                print(f"{path}: INVALID")
                for e in errs:
                    print(f"  - {e}")
            else:
                print(f"{path}: ok")
        return 1 if bad else 0

    if args.cmd == "expand":
        bench = _registry()[args.bench]
        for pt in expand(bench.config, select=_parse_select(args.select),
                         limit=args.limit):
            print(json.dumps({"coords": pt.coords, "seed": pt.seed}))
        return 0

    if args.cmd == "migrate":
        docs = migrate(root=args.root, write=args.write)
        for name, doc in sorted(docs.items()):
            print(f"{name}: {len(doc['rows'])} rows "
                  f"({'written' if args.write else 'dry-run'})")
        return 0


if __name__ == "__main__":
    # Whether invoked as `python -m benchmarks.matrix` or as a script, this
    # module is loaded as __main__ — alias it as benchmarks.matrix so
    # run.py's registrations land in THIS registry, not a second instance.
    if __package__ in (None, ""):
        sys.path.insert(0, _REPO_ROOT)
    sys.modules.setdefault("benchmarks.matrix", sys.modules["__main__"])
    import benchmarks
    benchmarks.matrix = sys.modules["benchmarks.matrix"]
    sys.exit(main())
