"""The harness is driven by data: a cell, a statement, a mix and a layer
metric added as NEW FILES (plus entries in BENCHMARK.json) in a temporary
copy are found and run with no edit to the harness; and the harness names
no cell, statement, parameter range or metric in its code."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import spec

B = spec.read_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))


def _last_json(text: str) -> dict:
    return json.loads(text.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def copy_with_additions(tmp_path_factory):
    root = tmp_path_factory.mktemp("copy")
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    bench = root / "benchmark"
    # a statement of its own (TPC-H Q6 without the quantity predicate)
    (bench / "statements" / "extra_scan.json").write_text(json.dumps({
        "sql": "select sum(l_extendedprice * l_discount) as revenue, "
               "count(*) as n from lineitem where l_shipdate >= date "
               "'{DATE}' and l_discount >= {LOW}",
        "parameters": {
            "DATE": {"kind": "date", "year_min": 1993, "year_max": 1996,
                     "validation": "1994-01-01"},
            "LOW": {"kind": "decimal", "min": "0.03", "max": "0.06",
                    "step": "0.01", "validation": "0.05"}},
        "reads": {"lineitem": ["l_shipdate", "l_discount",
                               "l_extendedprice"]},
        "ordered": True,
        "reference": {"sqlite": True}}))
    (bench / "traffic" / "extra_mix.json").write_text(json.dumps({
        "loop": "closed", "clients": 1, "order": "round_robin",
        "templates": [{"statement": "extra_scan", "params": {"pool": 3}},
                      {"statement": "tpch_q6", "params": "validation"}],
        "trace_executions": 2}))
    (bench / "layer_metrics" / "extra_counts.py").write_text(
        "def compute(record):\n"
        "    return len(record['window'])\n")
    b = json.loads(json.dumps(B))
    b["workloads"].append({
        "name": "tpch_sf1.extra", "config": "tpch_sf1",
        "traffic": "extra_mix", "chips": 1, "why": "added by a test"})
    b["per_layer"].append({
        "name": "extra_counts", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "session",
        "moves": "stmt_geomean_ms", "workloads": ["tpch_sf1.extra"]})
    # the four-chip PX cell measured in PR 23 and kept as files only (its
    # run does not fit the contract's 360 s yet, PERF.md section 7): one
    # configuration entry, one cell entry and one metric entry bring it back
    b["configs"].append({
        "name": "tpch_sf1_px4", "file": "benchmark/configs/tpch_sf1_px4.json",
        "source": spec.read_json(os.path.join(
            spec.BENCH_DIR, "configs", "tpch_sf1_px4.json"))["source"],
        "reduced": ["scale_factor"], "why": "the PX path"})
    b["workloads"].append({
        "name": "tpch_sf1_px4.mix", "config": "tpch_sf1_px4",
        "traffic": "mix", "chips": 4, "why": "added back by a test"})
    b["per_layer"].append({
        "name": "px_collective_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "PX", "moves": "stmt_geomean_ms",
        "workloads": ["tpch_sf1_px4.mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def _run(root, *args, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=spec.REPO_DIR)  # the program, for the adapter
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args], cwd=root, env=env,
        capture_output=True, text=True, timeout=timeout)


def test_added_files_are_found(copy_with_additions):
    cell = spec.Cell("tpch_sf1.extra",
                     bench_dir=str(copy_with_additions / "benchmark"))
    assert list(cell.statements) == ["extra_scan", "tpch_q6"]
    assert "extra_counts" in {m["name"] for m in cell.metrics("per_layer")}
    assert "extra_counts" not in {
        m["name"] for m in spec.Cell(
            "tpch_sf1.scan",
            bench_dir=str(copy_with_additions / "benchmark")
        ).metrics("per_layer")}


def test_the_px_cell_kept_as_files_comes_back_with_its_entries(
        copy_with_additions):
    cell = spec.Cell("tpch_sf1_px4.mix",
                     bench_dir=str(copy_with_additions / "benchmark"))
    assert cell.chips == 4 and cell.config["required_path"] == "px"
    assert cell.config["session_settings"] == ["set px_dop = 4"]
    assert list(cell.statements) == ["tpch_q1", "tpch_q3", "tpch_q6"]
    assert cell.tables() == ["lineitem", "customer", "orders"]
    assert "px_collective_ms" in {
        m["name"] for m in cell.metrics("per_layer")}


def test_added_cell_rehearses_end_to_end(copy_with_additions):
    """The temporary copy's own run.py, traced, on the CPU at SF0.01."""
    p = _run(copy_with_additions, "--workload", "tpch_sf1.extra", "--seed",
             "4", "--seconds", "1", "--trace", "1", "--rehearse", "0.01")
    assert p.returncode == 3, p.stderr[-3000:]  # rehearsed, no accelerator
    last = _last_json(p.stdout)
    assert last["rehearsal"] is True and last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] > 4
    assert last["device"]["platform"] == "cpu"
    assert last["metrics"]["extra_counts"]["value"] == last["attempted"] - 4
    assert last["metrics"]["compiles_in_window"]["value"] == 0
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
    ops = last["breakdown"]["device_ops"]
    assert 0 < len(ops) <= 10 and ops[0][0].startswith(("extra_scan:",
                                                        "tpch_q6:"))


def test_off_the_accelerator_there_is_no_result(copy_with_additions):
    p = _run(copy_with_additions, "--workload", "tpch_sf1.extra", "--seed",
             "4", "--seconds", "1", "--trace", "0")
    assert p.returncode not in (0, 3)
    assert '"correct"' not in p.stdout
    assert "not tpu" in p.stderr


def test_without_the_program_there_is_no_result(copy_with_additions):
    """A directory with only BENCHMARK.json and the files under paths."""
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tpch_sf1.extra",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=copy_with_additions, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and '"correct"' not in p.stdout


def _data_names() -> set:
    names = {w["name"] for w in B["workloads"]}
    names |= {c["name"] for c in B["configs"]}
    names |= {m["name"] for m in B["end_to_end"] + B["per_layer"]}
    for sub in ("end_to_end", "layer_metrics"):  # readers kept as files too
        names |= {os.path.splitext(f)[0] for f in os.listdir(
            os.path.join(spec.BENCH_DIR, sub)) if f.endswith(".py")}
    for sub in ("statements", "datasets", "references"):
        names |= {os.path.splitext(f)[0] for f in os.listdir(
            os.path.join(spec.BENCH_DIR, sub)) if not f.startswith("_")}
    # parameter names and range ends of the statement files
    for f in os.listdir(os.path.join(spec.BENCH_DIR, "statements")):
        st = spec.read_json(os.path.join(spec.BENCH_DIR, "statements", f))
        names |= set(st.get("parameters", {})) | set(st.get("derived", {}))
        names |= set(st.get("writes", ()))  # the tables a statement writes
    return names


def test_the_harness_names_no_cell_statement_or_metric():
    files = [os.path.join(spec.BENCH_DIR, "run.py")] + [
        os.path.join(spec.BENCH_DIR, "harness", f)
        for f in os.listdir(os.path.join(spec.BENCH_DIR, "harness"))
        if f.endswith(".py")]
    words = set()
    for path in files:
        with open(path, encoding="utf-8") as f:
            words |= set(re.findall(r"[A-Za-z0-9_.]+", f.read()))
    assert not (_data_names() & words), sorted(_data_names() & words)
