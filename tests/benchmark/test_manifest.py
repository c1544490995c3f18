"""BENCHMARK.json against the driver's rules for names, and against the files
the harness finds by name.  Every name, unit and layer is a case of its own."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

CELLS = {c["name"]: c for c in MANIFEST["workloads"]}
CONFIGS = {c["name"]: c for c in MANIFEST["configs"]}
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def _names():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            yield f"{group}:{entry['name']}", entry["name"]
    for cell in MANIFEST["workloads"]:
        yield f"traffic:{cell['name']}", cell["traffic"]
        yield f"config-of:{cell['name']}", cell["config"]
    for m in MANIFEST["per_layer"]:
        yield f"layer:{m['name']}", m["layer"]
    for c in MANIFEST["configs"]:
        for key in c["reduced"]:
            yield f"reduced:{c['name']}:{key}", key


@pytest.mark.parametrize("label,name", list(_names()), ids=[n for n, _ in _names()])
def test_name_alphabet(label, name):
    assert NAME.match(name), f"{label}: {name!r}"


@pytest.mark.parametrize("metric", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"layer", "moves"} if "layer" in metric else {"bound"}
    assert set(metric) <= allowed, set(metric) - allowed
    for cell in metric.get("workloads", []):
        assert cell in CELLS, cell


def test_top_level_keys_and_counts():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    assert len(json.dumps(MANIFEST)) < 64 * 1024
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in MANIFEST[group]]
        assert len(names) == len(set(names)), group
    assert len(set(m["name"] for m in METRICS)) == len(METRICS)


def test_end_to_end_set():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert len(e2e) - 1 <= 4
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.1, m
        assert m["source"] in ("host_clock", "device_trace")


def test_four_chip_cells():
    four = [c for c in CELLS.values() if c["chips"] == 4]
    assert all(c["chips"] in (1, 4) for c in CELLS.values())
    assert len(four) <= 1


def _reports(cell, group):
    return {m["name"] for m in MANIFEST[group] if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_files_and_metrics(cell):
    entry = CELLS[cell]
    assert entry["config"] in CONFIGS
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    assert os.path.isfile(os.path.join(ROOT, CONFIGS[entry["config"]]["file"]))
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "workloads", cell + ".json"))
    e2e = _reports(cell, "end_to_end")
    assert "setup_s" in e2e and len(e2e) >= 2
    assert _reports(cell, "per_layer")


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_config_entry(config):
    entry = CONFIGS[config]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(c["config"] == config for c in CELLS.values()), "used by no cell"
    assert entry["file"].startswith("benchmark/")
    with open(os.path.join(ROOT, entry["file"])) as f:
        body = json.load(f)
    assert body["reduced"] == entry["reduced"]
    for module in ("jobs/" + body["job"], "reference/" + body["reference"]):
        assert os.path.isfile(os.path.join(ROOT, "benchmark", module + ".py")), module
    assert body["limits"] and all(isinstance(v, (int, float)) for v in body["limits"].values())


@pytest.mark.parametrize("metric", MANIFEST["per_layer"], ids=[m["name"] for m in MANIFEST["per_layer"]])
def test_layer_metric_file(metric):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", metric["name"] + ".json")
    with open(path) as f:
        spec = json.load(f)
    for key in ("name", "layer", "moves", "unit", "better", "source", "workloads"):
        assert spec[key] == metric[key], key
    assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers", spec["reader"] + ".py"))
    # Every cell that reports the metric reports the end-to-end metric it moves.
    assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
    for cell in metric["workloads"]:
        assert metric["moves"] in _reports(cell, "end_to_end"), (cell, metric["moves"])


def test_command_names_nothing_outside_paths():
    assert 1 <= len(MANIFEST["command"]) <= 32
    for word in MANIFEST["command"]:
        assert not word.startswith("/") and ".." not in word
