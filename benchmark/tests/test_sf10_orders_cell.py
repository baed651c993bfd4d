"""The cell ``tpch_sf10_orders.q4q13q18``: its entries in BENCHMARK.json,
its files, the statements' ``reads`` against the generated tables, its
three per-layer readers over a fixture record, one rehearsal end to end
off the TPU, and a driver that shows five lower guarantees fail it."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import bytes_model, spec

CELL = "tpch_sf10_orders.q4q13q18"
CONFIG = "tpch_sf10_orders"
B = spec.read_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
OWN = ("semi_outer_join_share", "groupby_sorted_mlanes",
       "groupby_groups_fill_pct")
#: the accepted metrics whose lists took the cell (ISSUE 44, item 7)
APPENDED = ("device_wait_ms", "plan_onepass_roofline", "parse_ms",
            "result_fetch_ms", "dispatch_ms", "device_copy_build_s",
            "merge_probe_share", "groupby_masked_share",
            "groupby_scan_share", "join_compacted_share",
            "join_probe_lanes_share", "result_packed_share",
            "bulk_load_us_per_row", "analyze_table_s", "hbm_resident_gb",
            "work_area_resident_share")


def _reader(name):
    return spec.load_module("layer_metrics", name)


def test_the_cell_and_its_entries():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and not cell.writes()
    assert cell.entry["traffic"] == "q4q13q18"
    assert [(t["statement"], t["params"])
            for t in cell.traffic["templates"]] == [
        ("tpch_q4_sf10", "validation"), ("tpch_q13_sf10", "validation"),
        ("tpch_q18_sf10", "validation")]
    assert cell.traffic["trace_executions"] == 1
    assert cell.tables() == ["orders", "lineitem", "customer"]
    assert cell.config["dataset"] == {"generator": "tpch_pooled",
                                      "scale": 10.0}
    assert cell.config["system_settings"] == [
        "set global ob_sql_work_area_percentage = 80",
        "set global ob_query_timeout = 36000000000"]
    assert cell.config["session_settings"] == ["set px_dop = 1"]
    assert cell.config["queries"] == ["Q4", "Q13", "Q18"]
    # SQLite cannot load 76M rows inside a run: exact references only
    for name, st in cell.statements.items():
        assert st["reference"] == {
            "sqlite": False,
            "exact": name.replace("_sf10", "_exact")}, name
        assert st["ordered"] is True
    entry = next(c for c in B["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200 and len(cell.entry["why"]) <= 200
    assert entry["reduced"] == ["scale_factor", "queries"]
    assert set(entry["reduced"]) == set(cell.config["reduced"])
    assert B["configs"][-1]["name"] == CONFIG
    assert B["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in B["workloads"]) == 2
    mine = {m["name"]: m for m in B["per_layer"]
            if m.get("workloads") == [CELL]}
    assert tuple(mine) == OWN
    assert [m["name"] for m in B["per_layer"][-3:]] == list(OWN)
    for m in mine.values():
        assert (m["layer"], m["moves"], m["source"]) == (
            "operators", "stmt_geomean_ms", "program_counter")
    assert mine["groupby_sorted_mlanes"]["unit"] == "Mlanes/stmt"
    assert mine["groupby_sorted_mlanes"]["better"] == "lower"
    by_name = {m["name"]: m for m in B["per_layer"]}
    for name in APPENDED:
        assert by_name[name]["workloads"][-1] == CELL, name
    assert CELL not in by_name["warmup_compile_s"]["workloads"]
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "stmt_geomean_ms", "setup_s"}


def test_the_statements_are_the_specifications_text():
    cell = spec.Cell(CELL)
    q13, q18 = (cell.statements[f"tpch_q{n}_sf10"]["sql"] for n in (13, 18))
    assert ") as c_orders (c_custkey, c_count) group by c_count" in q13
    assert "not like '%{WORD1}%{WORD2}%'" in q13
    assert "having sum(l_quantity) > {QUANTITY})" in q18
    assert q18.endswith("order by o_totalprice desc, o_orderdate limit 100")
    want = {"tpch_q4_sf10": {"DATE": "1993-07-01"},
            "tpch_q13_sf10": {"WORD1": "special", "WORD2": "requests"},
            "tpch_q18_sf10": {"QUANTITY": 300}}
    for name, st in cell.statements.items():
        assert {k: p["validation"] for k, p in st["parameters"].items()} \
            == want[name]


def test_the_reads_name_columns_the_generator_makes():
    cell = spec.Cell(CELL)
    ds = spec.load_module("datasets", cell.config["dataset"]["generator"])
    tables, _types = ds.generate(0.001, 7)
    layouts = {}
    for table, columns in cell.reads().items():
        assert set(columns) <= set(tables[table]), table
        layouts[table] = {"capacity": 1024, "mask_itemsize": 1, "columns": {
            c: {"itemsize": 8, "valid_itemsize": 0} for c in tables[table]}}
    # what plan_onepass_roofline's bytes function reads of them
    for st in cell.statements.values():
        assert bytes_model.one_pass_bytes(st["reads"], layouts) > 0
    assert sorted(cell.reads()["lineitem"]) == [
        "l_commitdate", "l_orderkey", "l_quantity", "l_receiptdate"]


def _record(before: dict, after: dict, statements: int = 3) -> dict:
    return {"counters_before": before, "counters_after": after,
            "device": {"count": 1},
            "window": [{"error": None}] * statements + [{"error": "x"}]}


def test_the_three_readers_over_a_fixture_record():
    how = "plan.join_kinds{how=%s}"
    before = {how % "inner": 10.0, how % "semi": 4.0,
              "plan.groupby_sort_lanes": 1e6,
              "plan.groupby_out_lanes": 1000.0, "plan.groupby_groups": 10.0}
    after = {how % "inner": 12.0, how % "semi": 6.0, how % "left": 1.0,
             "plan.groupby_sort_lanes": 1e6 + 3 * 100_663_296,
             "plan.groupby_out_lanes": 1000.0 + 20_000_000,
             "plan.groupby_groups": 10.0 + 15_000_640}
    rec = _record(before, after)
    assert _reader(OWN[0]).compute(rec) == pytest.approx(60.0)
    assert _reader(OWN[1]).compute(rec) == pytest.approx(100.663296)
    assert _reader(OWN[2]).compute(rec) == pytest.approx(75.0032)
    # a window that ran nothing of the kind, and a program without the
    # counters (the parent's): nothing to read, nothing raised
    idle = _record(after, after)
    assert _reader(OWN[0]).compute(idle) is None
    assert _reader(OWN[1]).compute(idle) == 0.0
    assert _reader(OWN[2]).compute(idle) is None
    parent = _record({}, {"plan.join_probes{kind=merge}": 5.0})
    assert [_reader(n).compute(parent) for n in OWN] == [None] * 3
    assert _reader(OWN[1]).compute(_record(before, after, 0)) is None


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("sf10orders")
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    shutil.copy(os.path.join(spec.REPO_DIR, "BENCHMARK.json"), root)
    return root


ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_DIR)


def test_the_cell_rehearses_end_to_end(copy):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         str(2**31 + 4400011), "--seconds", "3", "--trace", "1",
         "--rehearse", "0.02"], cwd=copy, env=ENV, capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 3, p.stderr[-3000:]
    last = json.loads(p.stdout.splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] >= 6
    assert all(c["value"] == 0 for c in last["compared"].values())
    m = {k: v["value"] for k, v in last["metrics"].items()}
    # every reader whose list took the cell finds something to read off
    # the TPU too, but the roofline share (no peak is published for a CPU)
    assert set(OWN) | set(APPENDED) - {"plan_onepass_roofline"} <= set(m)
    assert m["compiles_in_window"] == 0
    assert m["work_area_resident_share"] == 100.0
    # Q4 one semi-join, Q13 one left join, Q18 a semi-join and two inner
    assert 55.0 <= m["semi_outer_join_share"] <= 65.0
    assert m["groupby_sorted_mlanes"] > 0
    assert 0 < m["groupby_groups_fill_pct"] <= 100.0
    assert m["groupby_scan_share"] == 100.0


def test_five_lower_guarantees_each_fail_the_comparison(copy):
    p = subprocess.run(
        [sys.executable, "benchmark/tests/drive_q18_faults.py", CELL,
         "4400000033", "0.02"], cwd=copy, env=ENV, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads([x for x in p.stdout.splitlines()
                      if x.startswith('{"quantity"')][-1])
    assert got["clean"]["failed"] == 0 and got["clean"]["attempted"] >= 6
    for run in ("lost_row", "weaker_statements", "count_star"):
        assert got[run]["checks"]["exact_differ"] > 0, run
        assert got[run]["checks"]["raised"] == 0, run
    assert got["lost_row"]["templates"] == ["tpch_q18_sf10"]
    # >= for >, a semi-join served as a join, an outer join as an inner one
    assert got["weaker_statements"]["templates"] == [
        "tpch_q13_sf10", "tpch_q18_sf10", "tpch_q4_sf10"]
    assert got["count_star"]["templates"] == ["tpch_q13_sf10"]
