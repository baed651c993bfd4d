"""BENCHMARK.json against the limits of the builder's contract, and against
the files it names."""

import os
import re

import pytest

from benchmark.harness import spec

B = spec.read_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
METRICS = B["end_to_end"] + B["per_layer"]
LINE = re.compile(r"^[^\t\n]{1,200}$")


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(B["run_seconds"], int) and 1 <= B["run_seconds"] <= 51
    assert B["paths"] == ["benchmark"]
    assert 1 <= len(B["command"]) <= 32
    assert all(LINE.match(w) and not w.startswith("/") and ".." not in w
               for w in B["command"])
    assert os.path.getsize(
        os.path.join(spec.REPO_DIR, "BENCHMARK.json")) <= 64 << 10


def _names():
    out = [(m["name"], "metric") for m in METRICS]
    out += [(c["name"], "config") for c in B["configs"]]
    out += [(k, "reduced") for c in B["configs"] for k in c["reduced"]]
    for w in B["workloads"]:
        out += [(w["name"], "workload"), (w["config"], "config"),
                (w["traffic"], "traffic")]
    return sorted(set(out))


@pytest.mark.parametrize("name,kind", _names())
def test_names_use_the_allowed_characters(name, kind):
    assert spec.NAME_RE.match(name), (kind, name)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entries(metric):
    end_to_end = metric in B["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert spec.UNIT_RE.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in spec.SOURCES
    cells = {w["name"] for w in B["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert LINE.match(metric["layer"])
        assert metric["moves"] in {m["name"] for m in B["end_to_end"]}
        # reported only where the metric it moves is
        moved = next(m for m in B["end_to_end"]
                     if m["name"] == metric["moves"])
        assert set(metric.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
        if metric["name"].endswith("_roofline"):
            assert metric["unit"] == "%"
    directory = "end_to_end" if end_to_end else "layer_metrics"
    assert callable(spec.load_module(directory, metric["name"]).compute)


def test_no_name_twice():
    for group in (METRICS, B["configs"], B["workloads"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in B["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in B["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("config", B["configs"], ids=lambda c: c["name"])
def test_config_entries(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert LINE.match(config["source"]) and LINE.match(config["why"])
    assert config["file"].startswith("benchmark/")
    assert len(config["reduced"]) <= 16
    body = spec.read_json(os.path.join(spec.REPO_DIR, config["file"]))
    assert body["name"] == config["name"]
    assert body["source"] == config["source"]
    for key in config["reduced"]:
        assert key in body and key in body["reduced"]
    assert any(w["config"] == config["name"] for w in B["workloads"])
    for key in ("guarantees", "stands_for", "assumed", "dataset", "chips"):
        assert key in body


@pytest.mark.parametrize("cell", B["workloads"], ids=lambda w: w["name"])
def test_cells_resolve_to_their_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and LINE.match(cell["why"])
    c = spec.Cell(cell["name"])
    assert c.tables() and c.metrics("per_layer")
    e2e = {m["name"] for m in c.metrics("end_to_end")}
    assert "setup_s" in e2e and len(e2e) >= 2


def test_setup_and_four_chip_share():
    assert any(m["name"] == "setup_s" for m in B["end_to_end"])
    four = sum(1 for w in B["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(B["workloads"]) // 2)
    assert 2 <= len(B["workloads"]) <= 24


def test_files_under_paths_are_named_from_the_allowed_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root, dirs, files in os.walk(spec.BENCH_DIR):
        dirs[:] = [d for d in dirs if d not in (".scratch", "__pycache__")]
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), spec.REPO_DIR)
            assert ok.match(rel), rel
