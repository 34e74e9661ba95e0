"""The benchmark is driven by files: ``BENCHMARK.json`` keeps to its format,
every name it gives has its file, and a new cell or per-layer metric is
picked up by name from new files alone."""
import filecmp
import json
import os
import re
import shutil

from benchmarks.chip import spec
from benchmarks.chip.tests.tiny import HERE, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _bench():
    return spec.load_benchmark(ROOT)


def test_benchmark_json_format():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks/chip"]
    assert 1 <= b["run_seconds"] <= 51
    names = [c["name"] for c in b["configs"]] + \
        [w["name"] for w in b["workloads"]] + \
        [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmarks/chip/configs/")
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in b["workloads"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
    fours = sum(w["chips"] == 4 for w in b["workloads"])
    assert fours <= max(1, len(b["workloads"]) // 2)
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_every_cell_loads_and_reports_what_it_must():
    b = _bench()
    for w in b["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        e2e = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
            assert callable(spec.metric_reader(m["name"]))


def test_listed_cells_report_the_metric_they_move():
    b = _bench()
    reports = {w["name"]: {m["name"] for m in spec.load_cell(
        ROOT, w["name"]).end_to_end} for w in b["workloads"]}
    for m in b["per_layer"] + b["end_to_end"]:
        for cell in m.get("workloads", []):
            assert cell in reports, (m["name"], cell)
            if "moves" in m:
                assert m["moves"] in reports[cell], (m["name"], cell)


def test_a_new_cell_and_metric_are_found_by_name(tmp_path):
    root = str(tmp_path)
    here = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    b = _bench()
    # new files only: a traffic mix, a cell, a per-layer metric
    with open(os.path.join(here, "traffic", "mixes",
                           "fl-m2h8-b1-s512.json"), "w") as f:
        json.dump({"clients": 2, "local_steps": 8, "batch": 1,
                   "seq_len": 512, "source": "markov", "n_chains": 4,
                   "branching": 8}, f)
    shutil.copy(os.path.join(here, "workloads",
                             "train-qwen2-0.5b-m2h2-s512.json"),
                os.path.join(here, "workloads",
                             "train-qwen2-0.5b-m2h8-s512.json"))
    with open(os.path.join(here, "metrics", "rounds_seen.train.py"),
              "w") as f:
        f.write("def read(ctx):\n    return float(ctx.rounds)\n")
    # and their entries in the registry
    b["workloads"].append({"name": "train-qwen2-0.5b-m2h8-s512",
                           "config": "qwen2-0.5b-l16",
                           "traffic": "fl-m2h8-b1-s512", "chips": 1,
                           "why": "sync amortised over 8 local steps"})
    b["per_layer"].append({"name": "rounds_seen.train", "unit": "rounds",
                           "better": "higher", "source": "host_clock",
                           "layer": "round step",
                           "moves": "train_tokens_per_s",
                           "workloads": ["train-qwen2-0.5b-m2h8-s512"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    for c in b["configs"]:
        os.makedirs(os.path.dirname(os.path.join(root, c["file"])),
                    exist_ok=True)

    cell = spec.load_cell(root, "train-qwen2-0.5b-m2h8-s512", here=here)
    assert cell.mix.local_steps == 8 and cell.mix.tokens_per_round == 8192
    assert cell.config["num_hidden_layers"] == 16
    assert [m["name"] for m in cell.per_layer] == [
        "device_idle_pct.train", "mfu_pct.train", "rounds_seen.train"]
    read = spec.metric_reader("rounds_seen.train", here=here)
    assert read(type("ctx", (), {"rounds": 3})) == 3.0
    old = spec.load_cell(root, "train-qwen2-0.5b-m2h2-s512", here=here)
    assert "rounds_seen.train" not in [m["name"] for m in old.per_layer]
    # no file the benchmark already had was touched
    _same(filecmp.dircmp(HERE, here, ignore=["__pycache__", "tests"]))


def _same(cmp):
    assert not cmp.diff_files and not cmp.left_only, cmp.report()
    for sub in cmp.subdirs.values():
        _same(sub)
