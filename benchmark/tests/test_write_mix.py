"""A mix that writes, and a run's own time limit, end to end on the CPU:
the refresh cell kept as files comes back with its entries in a temporary
copy, rehearses with nothing failed, and each of three faults turns
``failed`` non-zero; a run over its limit ends itself with exit code 4."""

import json
import os
import shutil
import signal
import subprocess
import sys
import time

import pytest

from benchmark.harness import budget, spec

B = spec.read_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
CELL = "tpch_sf1_rf.refresh"


@pytest.fixture(scope="module")
def copy_with_the_refresh_cell(tmp_path_factory):
    root = tmp_path_factory.mktemp("refresh")
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    b = json.loads(json.dumps(B))
    b["configs"].append({
        "name": "tpch_sf1_rf", "file": "benchmark/configs/tpch_sf1_rf.json",
        "source": spec.read_json(os.path.join(
            spec.BENCH_DIR, "configs", "tpch_sf1_rf.json"))["source"],
        "reduced": ["scale_factor"], "why": "reads under committed writes"})
    b["workloads"].append({
        "name": CELL, "config": "tpch_sf1_rf", "traffic": "refresh",
        "chips": 1, "why": "added back by a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


def _run(root, script, *args, timeout=300):
    """-> (exit code, stdout, stderr, whether the process group is empty
    afterwards: the run left no process behind)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_DIR)
    p = subprocess.Popen(
        [sys.executable, script, *args], cwd=root, env=env,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        start_new_session=True)
    try:
        out, err = p.communicate(timeout=timeout)
    finally:
        alone = False
        for _ in range(50):     # multiprocessing's tracker needs a moment
            try:
                os.killpg(p.pid, 0)
            except ProcessLookupError:
                alone = True
                break
            time.sleep(0.1)
        if not alone:
            os.killpg(p.pid, signal.SIGKILL)
    return p.returncode, out, err, alone


def _lines(text: str) -> list[dict]:
    return [json.loads(line) for line in text.splitlines()
            if line.startswith("{")]


def test_the_refresh_cell_comes_back_with_its_entries(
        copy_with_the_refresh_cell):
    cell = spec.Cell(CELL,
                     bench_dir=str(copy_with_the_refresh_cell / "benchmark"))
    assert cell.writes() and cell.chips == 1
    assert cell.tables() == ["orders", "lineitem"]
    assert cell.reads() == {"lineitem": [
        "l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]}
    assert cell.config["guarantees"]["read_back"] == ["orders", "lineitem"]
    assert not spec.Cell("tpch_sf1.scan").writes()


def test_a_write_statement_takes_the_sequence_rule_and_no_other(
        copy_with_the_refresh_cell):
    bench = copy_with_the_refresh_cell / "benchmark"
    mix = spec.read_json(str(bench / "traffic" / "refresh.json"))
    mix["templates"][0]["params"] = "validation"
    (bench / "traffic" / "broken.json").write_text(json.dumps(mix))
    b = spec.read_json(str(copy_with_the_refresh_cell / "BENCHMARK.json"))
    b["workloads"].append({
        "name": "tpch_sf1_rf.broken", "config": "tpch_sf1_rf",
        "traffic": "broken", "chips": 1, "why": "a test"})
    other = copy_with_the_refresh_cell / "other"
    other.mkdir()
    (other / "BENCHMARK.json").write_text(json.dumps(b))
    os.symlink(bench, other / "benchmark")
    with pytest.raises(spec.SpecError, match="sequence"):
        spec.Cell("tpch_sf1_rf.broken", bench_dir=str(other / "benchmark"))


def test_the_refresh_cell_rehearses_end_to_end(copy_with_the_refresh_cell):
    rc, out, err, alone = _run(
        copy_with_the_refresh_cell, "benchmark/run.py", "--workload", CELL,
        "--seed", "11", "--seconds", "2", "--trace", "1", "--rehearse",
        "0.01")
    assert rc == 3, err[-3000:]     # rehearsed, no accelerator
    assert alone
    lines = _lines(out)
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] >= 9
    assert list(last)[-1] == "compared"     # the last key, by contract
    assert all(c["value"] <= c["limit"] for c in last["compared"].values())
    assert err.strip().splitlines()[-1].startswith("compared ")
    phases = {line["phase"]: line for line in lines if "phase" in line}
    assert phases["compare"]["reference_seconds"]["answered"] >= 1
    got = phases["read_back"]["tables"]
    assert set(got) == {"orders", "lineitem"}
    # every write of the run is one set; none came twice
    record = spec.read_json(str(
        copy_with_the_refresh_cell / "benchmark" / ".scratch" / "runs"
        / f"{CELL}.seed11.trace1.json"))["record"]
    sets = [(r["template"], r["k"])
            for part in ("warmup", "window", "traced")
            for r in record[part] if "k" in r]
    assert len(sets) == len(set(sets)) >= 6
    assert all(all(r["acks"]) for part in ("warmup", "window", "traced")
               for r in record[part] if "k" in r)
    assert record["read_back"]["orders"]["got"] == \
        record["read_back"]["orders"]["want"]


def test_three_faults_are_each_caught(copy_with_the_refresh_cell):
    rc, out, err, _ = _run(
        copy_with_the_refresh_cell, "benchmark/tests/drive_mutations.py",
        CELL, "5", "0.01")
    assert rc == 0, err[-3000:]
    got = _lines(out)[-1]
    attempted, failed = got["clean"]
    assert failed == 0 and attempted >= 4
    assert got["window"]["writes"] >= 1 and got["window"]["reads"] >= 1
    assert got["dropped_write"][1] > 0      # missing from the replay
    assert got["swapped"] is not None and got["swapped"][1] > 0
    assert got["lost_row"][1] > 0 and got["checks"]["read_back_differ"] > 0


def test_a_run_over_its_limit_ends_itself(copy_with_the_refresh_cell):
    """The limit falls in the load: exit code 4, one line that names the
    phase, no result line, no process left."""
    rc, out, err, alone = _run(
        copy_with_the_refresh_cell, "benchmark/tests/drive_short_limit.py",
        "5", "--workload", CELL, "--seed", "3", "--seconds", "1", "--trace",
        "0", "--rehearse", "0.01")
    assert rc == budget.EXIT_OVER_BUDGET == 4, err[-3000:]
    last = _lines(out)[-1]
    assert last["phase"] == "over_budget" and last["limit_s"] == 5
    assert last["in"] in ("device", "boot", "generate", "load")
    assert 3.5 <= last["elapsed_s"] < 5
    assert '"correct"' not in out
    assert alone


def test_a_window_that_cannot_fit_is_not_spent(copy_with_the_refresh_cell):
    """Set-up fits, set-up plus ``--seconds`` does not: the run ends at the
    window's start, long before its limit."""
    rc, out, err, alone = _run(
        copy_with_the_refresh_cell, "benchmark/tests/drive_short_limit.py",
        "60", "--workload", "tpch_sf1.scan", "--seed", "3", "--seconds",
        "58", "--trace", "0", "--rehearse", "0.01")
    assert rc == 4, err[-3000:]
    last = _lines(out)[-1]
    assert last["phase"] == "over_budget" and last["in"] == "window"
    assert last["elapsed_s"] < 30 and "warmup" in last["phases"]
    assert '"correct"' not in out and alone


def test_the_first_run_of_a_cell_in_a_checkout_has_the_longer_limit(tmp_path):
    marker = str(tmp_path / "started" / "a_cell")
    first = budget.Budget(time.monotonic(), marker)
    later = budget.Budget(time.monotonic(), marker)
    try:
        assert first.first_run and first.limit_s == budget.FIRST_RUN_S == 1200
        assert not later.first_run and later.limit_s == budget.CONTRACT_S == 360
        assert budget.MARGIN_S == 15
    finally:
        first.close()
        later.close()
    rehearsal = budget.Budget(time.monotonic(), marker + ".rehearsal")
    rehearsal.close()
    assert rehearsal.first_run      # a rehearsal leaves a marker of its own
