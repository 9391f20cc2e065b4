"""The manifest and every data file it names validate, on the CPU, with
nothing loaded at import."""

import importlib.util
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")
sys.path.insert(0, BENCH)  # the metric readers import ``lib``
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def read(rel):
    with open(os.path.join(REPO, rel)) as f:
        return json.load(f)


def by_kind(kind):
    return [pytest.param(x, id=x["name"]) for x in manifest()[kind]]


def test_manifest_shape():
    m = manifest()
    assert set(m) == KEYS
    assert m["command"] == ["python3", "benchmarks/run.py"]
    assert m["paths"] == ["benchmarks", "tests/benchmarks"]
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    # a full check with all 24 cells has to fit its allowance
    assert (2 + 14 * 24) * (m["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(m)) < 64 * 1024
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [x["name"] for x in m[kind]]
        assert len(names) == len(set(names)), f"duplicate names in {kind}"
    metric_names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(m["workloads"]) // 4)
    assert "setup_s" in {x["name"] for x in m["end_to_end"]}


@pytest.mark.parametrize("config", by_kind("configs"))
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert config["file"].startswith("benchmarks/configs/")
    data = read(config["file"])
    assert data["name"] == config["name"]
    assert data["source"] == config["source"]
    assert data["reduced"] == config["reduced"]
    model = data["model"]
    assert model["d_model"] == model["n_heads"] * model["head_dim"]
    published = data["published"]
    assert (model["d_model"], model["n_layers"], model["n_heads"]) == (
        published["n_embd"], published["n_layer"], published["n_head"])
    assert model["seq_len"] == published["n_positions"]
    assert model["vocab_real"] == published["vocab_size"]
    assert any(
        w["config"] == config["name"] for w in manifest()["workloads"]
    ), "a configuration no cell uses"


@pytest.mark.parametrize("cell", by_kind("workloads"))
def test_cell_entry(cell):
    m = manifest()
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in m["configs"]}
    data = read(f"benchmarks/workloads/{cell['name']}.json")
    assert data["name"] == cell["name"]
    assert os.path.exists(os.path.join(BENCH, "drivers", data["driver"] + ".py"))
    read(f"benchmarks/traffic/{cell['traffic']}.json")
    # what the cell's file says it reports is what the manifest expects of it
    expected = {
        e["name"] for e in m["end_to_end"]
        if cell["name"] in e.get("workloads", [cell["name"]])
    }
    assert set(data["end_to_end"]) == expected
    assert "setup_s" in expected and len(expected) >= 2
    assert any(
        cell["name"] in p.get("workloads", [cell["name"]])
        for p in m["per_layer"]
    ), "a cell without a per-layer metric"
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert pairs.count((cell["config"], cell["traffic"])) == 1
    for name, limit in data["limits"].items():
        assert isinstance(limit, (int, float)) and limit >= 0, name
    assert "PLACEHOLDER" not in data.get("limits_why", "")


@pytest.mark.parametrize("metric", by_kind("end_to_end"))
def test_end_to_end_entry(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    cells = {w["name"] for w in manifest()["workloads"]}
    assert set(metric.get("workloads", [])) <= cells


@pytest.mark.parametrize("metric", by_kind("per_layer"))
def test_per_layer_entry(metric):
    m = manifest()
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    moved = next(e for e in m["end_to_end"] if e["name"] == metric["moves"])
    for cell in metric["workloads"]:
        # every cell the metric lists reports the end-to-end metric it moves
        assert cell in moved.get("workloads", [cell]), (cell, moved["name"])
    path = os.path.join(BENCH, "metrics", metric["name"] + ".py")
    spec = importlib.util.spec_from_file_location("m", path)
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert callable(reader.read)
    for key in ("name", "layer", "unit", "source", "moves"):
        assert reader.META[key] == metric[key], key
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_every_file_under_paths_is_plainly_named():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for top in manifest()["paths"]:
        for folder, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), REPO)
                assert ok.match(rel), rel
