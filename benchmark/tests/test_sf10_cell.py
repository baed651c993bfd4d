"""The cell ``tpch_sf10.q1q6q14``: its files resolve, its statements are
held to exact references only, a program that loses one row of Q14's month
or sums in float32 is found by the reference's tolerance, and its four
per-layer readers read what they say."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import spec

CELL = "tpch_sf10.q1q6q14"
B = spec.read_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
ACCEPTED = ["tpch_sf1.heavy", "tpch_sf1.scan", "tpch_sf1_part4.mix",
            "tpch_sf1_htap.fresh"]


def _reader(name):
    return spec.load_module("layer_metrics", name)


@pytest.fixture()
def copy_of_the_benchmark(tmp_path):
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    shutil.copy(os.path.join(spec.REPO_DIR, "BENCHMARK.json"), tmp_path)
    return tmp_path


def test_the_cell_resolves_to_its_files():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and not cell.writes()
    assert [(t["statement"], t["params"])
            for t in cell.traffic["templates"]] == [
        ("tpch_q1_sf10", "validation"), ("tpch_q6", {"pool": 16}),
        ("tpch_q14_sf10", "validation")]
    assert cell.traffic["trace_executions"] == 2
    assert cell.tables() == ["lineitem", "part"]
    assert cell.config["dataset"] == {"generator": "tpch_pooled",
                                      "scale": 10.0}
    assert cell.config["system_settings"] == \
        ["set global ob_sql_work_area_percentage = 80"]
    # SQLite cannot load 60M rows inside a run: exact references only
    assert all(st["reference"]["sqlite"] is False and st["reference"]["exact"]
               for st in cell.statements.values())
    by_name = {m["name"]: m for m in B["per_layer"]}
    for name in ("bulk_load_us_per_row", "analyze_table_s", "hbm_resident_gb"):
        assert by_name[name]["workloads"] == ACCEPTED + [CELL]
        assert by_name[name]["moves"] == "setup_s"
    assert by_name["work_area_resident_share"]["workloads"] == [CELL]
    assert by_name["warmup_compile_s"]["workloads"] == ACCEPTED
    # the accepted readers whose layer runs in the cell report there too
    for name in ("device_wait_ms", "plan_onepass_roofline", "parse_ms",
                 "result_fetch_ms", "dispatch_ms", "device_copy_build_s",
                 "groupby_masked_share", "join_compacted_share",
                 "result_packed_share"):
        assert by_name[name]["workloads"][-1] == CELL
    reported = {m["name"] for m in cell.metrics("end_to_end")}
    assert reported == {"stmt_geomean_ms", "setup_s"}


@pytest.mark.parametrize("scale,seed", [(0.01, 5), (0.02, 3800000101),
                                        (0.005, 2**31 + 7)])
def test_the_pooled_generator_gives_tpchs_tables(scale, seed):
    """Array for array: the same draws in the same order, strings from a
    pool where ``tpch.py`` makes one a row."""
    want, want_types = spec.load_module("datasets", "tpch").generate(
        scale, seed)
    pooled = spec.load_module("datasets", "tpch_pooled")
    got, got_types = pooled.generate(scale, seed)
    assert got_types == want_types and list(got) == list(want)
    for table, columns in want.items():
        assert list(got[table]) == list(columns), table
        for name, array in columns.items():
            mine = got[table][name]
            assert mine.dtype == array.dtype and mine.shape == array.shape
            assert (mine == array).all(), (table, name)
    assert pooled.PRIMARY_KEYS == spec.load_module(
        "datasets", "tpch").PRIMARY_KEYS
    with open(os.path.join(spec.BENCH_DIR, "datasets",
                           "tpch_pooled.py"), encoding="utf-8") as f:
        assert "oceanbase_tpu" not in f.read()


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(spec.BENCH_DIR, "references",
                           "tpch_q14_exact.py"), encoding="utf-8") as f:
        assert "oceanbase_tpu" not in f.read().split('"""', 2)[2]


def test_a_lost_row_and_float32_sums_are_found_by_the_reference(
        copy_of_the_benchmark):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_DIR)
    p = subprocess.run(
        [sys.executable, "benchmark/tests/drive_q14_faults.py", CELL,
         "3800000031", "0.05"], cwd=copy_of_the_benchmark, env=env,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads([x for x in p.stdout.splitlines()
                      if x.startswith('{"clean"')][-1])
    assert got["clean"]["failed"] == 0 and got["clean"]["attempted"] >= 6
    for fault in ("dropped_row", "float32"):
        assert got[fault]["checks"]["exact_differ"] > 0, fault
        assert got[fault]["checks"]["raised"] == 0
        assert "tpch_q14_sf10" in got[fault]["templates"], fault
    # the float32 statement is Q14's alone; a lost row is Q1's loss too
    assert got["float32"]["templates"] == ["tpch_q14_sf10"]
    assert got["dropped_row"]["fault"] >= 0


def test_the_four_readers():
    rec = {"counters_before": {
        "storage.bulk_load_rows": 2000.0,
        "storage.bulk_load_ns{phase=encode}": 1.5e6,
        "storage.bulk_load_ns{phase=persist}": 0.5e6,
        "storage.analyze_ns": 2.5e9,
        "sql.work_area_decisions{kind=resident}": 10.0},
        "counters_after": {
        "storage.device_copy_bytes": 6.5e9,
        "sql.work_area_decisions{kind=resident}": 40.0,
        "sql.work_area_decisions{kind=spill}": 10.0}}
    assert _reader("bulk_load_us_per_row").compute(rec) == pytest.approx(1.0)
    assert _reader("analyze_table_s").compute(rec) == pytest.approx(2.5)
    assert _reader("hbm_resident_gb").compute(rec) == pytest.approx(6.5)
    assert _reader("work_area_resident_share").compute(rec) == \
        pytest.approx(75.0)
    # the parent's side of an accepted cell: no such counter, nothing raised
    empty = {"counters_before": {}, "counters_after": {}}
    for name in ("bulk_load_us_per_row", "analyze_table_s", "hbm_resident_gb",
                 "work_area_resident_share"):
        assert _reader(name).compute(empty) is None
