"""What one cell is, read from files by name: ``BENCHMARK.json`` at the
root names the cell's configuration, traffic mix and chips; the
configuration is ``configs/<config>.json``, the mix
``traffic/mixes/<traffic>.json``, and the cell's method and check
limits ``workloads/<cell>.json``. A per-layer metric is read by
``metrics/<name>.py``. A configuration's ``model_type`` names two files:
``model_types/<model_type>.py``, what the program needs to know of the
type (its configuration keys as the program names them, the depth key,
the numbers the file sets, any further check, the forward FLOPs of a
token), and ``reference/<model_type>.py``, its plain reference
(``param_table``, ``init_params``, ``loss``). A new cell, configuration
or metric is a new file, and a new model type those two; no file that is
already there changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

from benchmarks.chip.traffic.generator import Mix, load_mix

HERE = os.path.dirname(os.path.abspath(__file__))
# where ``model_types/`` and ``reference/`` are found, read at each lookup
TYPES_DIR = HERE


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict            # the configuration file
    traffic_name: str
    mix: Mix
    workload: dict          # method, check
    end_to_end: list        # BENCHMARK.json entries this cell reports
    per_layer: list


def _read(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str) -> dict:
    return _read(os.path.join(root, "BENCHMARK.json"))


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str, here: str = HERE) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files read
    from the benchmark directory ``here``."""
    bench = load_benchmark(root)
    entry = [w for w in bench["workloads"] if w["name"] == name]
    if len(entry) != 1:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in bench['workloads']]}")
    entry = entry[0]
    conf_entry = [c for c in bench["configs"] if c["name"] == entry["config"]]
    if len(conf_entry) != 1:
        raise SystemExit(f"workload {name}: no config {entry['config']!r}")
    config = _read(os.path.join(root, conf_entry[0]["file"]))
    workload = _read(os.path.join(here, "workloads", f"{name}.json"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    per_layer = [m for m in bench["per_layer"] if _reports(m, name)]
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], config=config,
                traffic_name=entry["traffic"],
                mix=load_mix(entry["traffic"],
                             os.path.join(here, "traffic", "mixes")),
                workload=workload, end_to_end=e2e, per_layer=per_layer)


def _module(here: str, kind: str, name: str):
    """The module ``<here>/<kind>/<name>.py``."""
    path = os.path.join(here, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise SystemExit(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: str = HERE):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return _module(here, "metrics", name).read


def model_type(kind: str):
    """``model_types/<kind>.py``: ``FIELDS`` (configuration key ->
    ``ModelConfig`` field, dotted for a nested one), ``DEPTH`` (the key of
    the layer count), ``SET`` (configuration key -> field, numbers the file
    sets on the registered architecture), ``forward_flops(conf, S)`` and,
    where the type has one, ``check(cfg)``."""
    return _module(TYPES_DIR, "model_types", kind)


def reference(kind: str):
    """``reference/<kind>.py``: the plain reference of the model type,
    ``param_table(conf)``, ``init_params(key, cfg=conf)`` and
    ``loss(conf, params, tokens, labels)``."""
    return _module(TYPES_DIR, "reference", kind)
