"""The four-node SF10 deployment (``tpch_sf10_part4``): its entries in
BENCHMARK.json, its configuration's DDL and settings, the Q9 reference, one
rehearsal of its cell end to end on four virtual CPU devices, the readers
of its four metrics, and a driver that shows two faults fail it."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import exchange_bytes_model, spec, xplane

CELL = "tpch_sf10_part4.q9q14"
B = spec.read_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
OWN = ("px_repartition_join_share", "px_exchange_fill_pct",
       "px_exchange_overflows", "px_exchange_roofline")


def test_the_cell_and_its_entries():
    cell = spec.Cell(CELL)
    assert cell.chips == 4 and cell.entry["traffic"] == "q9q14"
    assert cell.config["required_path"] == "px"
    assert cell.config["session_settings"] == ["set px_dop = 4"]
    assert cell.config["dataset"] == {"generator": "tpch_pooled",
                                      "scale": 10.0}
    assert list(cell.statements) == ["tpch_q9_sf10", "tpch_q14_sf10"]
    assert cell.traffic["trace_executions"] == 1
    for st in cell.statements.values():
        assert st["reference"]["sqlite"] is False and st["reference"]["exact"]
    entry = next(c for c in B["configs"] if c["name"] == "tpch_sf10_part4")
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200 and len(cell.entry["why"]) <= 200
    assert entry["reduced"] == ["scale_factor", "partitions", "queries"]
    assert set(entry["reduced"]) == set(cell.config["reduced"])
    mine = {m["name"]: m for m in B["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == set(OWN)
    assert {m["layer"] for m in mine.values()} == {"PX"}
    assert mine["px_exchange_overflows"]["moves"] == "setup_s"
    assert mine["px_exchange_roofline"]["source"] == "device_trace"
    # the last entries of their lists: nothing before them moved
    assert [m["name"] for m in B["per_layer"][-4:]] == list(OWN)
    assert B["workloads"][-1]["name"] == CELL
    assert B["configs"][-1]["name"] == "tpch_sf10_part4"
    assert sum(w["chips"] == 4 for w in B["workloads"]) == 2
    listed = {m["name"] for m in cell.metrics("per_layer")}
    assert listed >= set(OWN) | {"bind_ms", "host_ms", "stall_s", "load_s",
                                 "device_idle_pct", "hbm_peak_gb",
                                 "capacity_retries", "compiles_in_window"}
    assert "pwj_join_share" not in listed
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "stmt_geomean_ms", "setup_s"}


def test_the_settings_and_the_layout_are_the_guides():
    cell = spec.Cell(CELL)
    settings = cell.config["system_settings"]
    assert settings[:2] == [
        "set global ob_sql_work_area_percentage = 80",
        "set global parallel_servers_target = 128"]
    groups = [s.split()[2] for s in settings
              if s.startswith("create tablegroup")]
    assert len(groups) == 2
    ddl = {s.split()[2]: s for s in settings
           if s.startswith("create table ")}
    assert sorted(ddl) == sorted(cell.tables())
    dataset = spec.load_module("datasets", "tpch_pooled")
    tables, _types = dataset.generate(0.001, 1)
    keys = {"lineitem": "l_orderkey", "orders": "o_orderkey",
            "partsupp": "ps_partkey", "part": "p_partkey",
            "supplier": "s_suppkey"}
    for name, sql in ddl.items():
        for col in tables[name]:            # every loaded column, typed
            assert f"{col} " in sql
        pk = ", ".join(dataset.PRIMARY_KEYS[name])
        assert f"primary key ({pk})" in sql
        if name == "nation":
            assert "partition" not in sql
        else:
            assert f"partition by key ({keys[name]}) partitions 4" in sql
    for a, b in (("lineitem", "orders"), ("partsupp", "part")):
        (group,) = {g for g in groups if f"tablegroup = {g} " in ddl[a]}
        assert f"tablegroup = {group} " in ddl[b]
    assert "tablegroup" not in ddl["supplier"]


def test_q9_reference_on_a_join_made_by_hand():
    """Two green lineitems and one red: the sums, the year, the order."""
    ref = spec.load_module("references", "tpch_q9_exact")
    day = lambda s: int((np.datetime64(s) - np.datetime64("1970-01-01"))
                        .astype(np.int64))
    tables = {
        "part": {"p_partkey": np.array([1, 2]),
                 "p_name": np.array(["dark green tan", "red"], object)},
        "supplier": {"s_suppkey": np.array([7, 8]),
                     "s_nationkey": np.array([0, 1])},
        "nation": {"n_nationkey": np.array([0, 1]),
                   "n_name": np.array(["PERU", "CHINA"], object)},
        "partsupp": {"ps_partkey": np.array([1, 1, 2]),
                     "ps_suppkey": np.array([7, 8, 7]),
                     "ps_supplycost": np.array([100, 200, 300])},
        "orders": {"o_orderkey": np.array([10, 11]),
                   "o_orderdate": np.array([day("1995-03-01"),
                                            day("1996-01-01")], np.int32)},
        "lineitem": {"l_orderkey": np.array([10, 11, 11, 10]),
                     "l_partkey": np.array([1, 1, 2, 1]),
                     "l_suppkey": np.array([7, 8, 7, 9]),   # 9: no partsupp
                     "l_quantity": np.array([200, 100, 100, 100]),
                     "l_extendedprice": np.array([5000, 7000, 900, 100]),
                     "l_discount": np.array([10, 0, 0, 0])}}
    assert ref.answer(tables, {"COLOR": "green"}) == [
        ("CHINA", 1996, 7000 * 100 - 200 * 100),
        ("PERU", 1995, 5000 * 90 - 100 * 200)]
    got = ref.extract(["nation", "o_year", "sum_profit"], {
        "nation": np.array(["CHINA"], object), "o_year": np.array([1996]),
        "sum_profit": np.array([680000])})
    assert got == [("CHINA", 1996, 680000)]


# a chip's capture as the TPU's profiler names it: an instruction JAX
# lowered keeps its primitive's name (all_to_all), one the compiler made has
# the opcode's (all-reduce); an asynchronous all-gather is a span of the
# second line.  Device 0: all-to-all 10-40 us, all-reduce 35-50 us (they
# overlap: 40 us together), a fusion; device 1: an all-gather of 20 us
_CAPTURE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 30000000 }
    events { metadata_id: 2 offset_ps: 35000000 duration_ps: 15000000 }
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 90000000 } }
  event_metadata { key: 1 value { id: 1 name: "%all_to_all.42 = u32[4,1,8]{2,1,0} all-to-all(u32[4,1,8]{2,1,0} %fusion.3)" } }
  event_metadata { key: 2 value { id: 2 name: "%all-reduce.2 = (u32[4]{0}, u32[4]{0}) all-reduce(u32[4]{0} %a, u32[4]{0} %b)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = u32[32]{0} fusion(u32[64]{0} %p), kind=kCustom" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 60000000 duration_ps: 90000000 } }
  lines { id: 2 name: "Async XLA Ops" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 20000000 duration_ps: 20000000 } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3 = u32[32]{0} fusion(u32[64]{0} %p), kind=kCustom" } }
  event_metadata { key: 4 value { id: 4 name: "%all_gather.7 = u32[32]{0} all-gather-start(u32[8]{0} %q)" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:execute:a" } } }
"""


def test_a_collective_is_known_by_its_opcode():
    reader = spec.load_module("layer_metrics", "px_exchange_roofline")
    for label in ("all_to_all.42 all-to-all u32[4,1,147456]<-(u32[4,1,147456])",
                  "all-reduce.2 all-reduce (u32[4],u32[4])<-(u32[4],u32[4])",
                  "all_gather.7 all-gather-start u32[32]<-(u32[8])",
                  "all-to-all.2", "all-gather", "All-Reduce.1"):
        assert reader.is_collective(label), label
    for label in ("fusion.124 fusion/kCustom s32[1966080]<-(s32[16777216])",
                  "sort.400 sort (u32[8])<-(u32[8])", "copy-start.1", "fusion"):
        assert not reader.is_collective(label), label
    profile = xplane.load_text(_CAPTURE)
    assert reader.collective_seconds(profile) == pytest.approx(40e-6)
    # what the reader may not use: the reduction's collective_s knows a
    # collective by its instruction's name, and misses the all_to_all
    red = xplane.reduce_capture(profile, "bench:")
    assert red["devices"][0]["collective_s"] == pytest.approx(15e-6)
    assert red["devices"][1]["collective_s"] == 0.0


def test_the_four_readers(monkeypatch, tmp_path):
    rec = {"device": {"count": 4, "kind": "TPU v5 lite"},
           "cell": {"name": CELL},
           "counters_before": {
               "px.joins{dist=pkey}": 3.0, "px.joins{dist=broadcast}": 3.0,
               "px.exchange_lanes{kind=pkey}": 1000.0,
               "px.exchange_rows{kind=pkey}": 100.0,
               "px.exchange_bytes{kind=pkey}": 5000.0},
           "counters_after": {
               "px.joins{dist=pkey}": 6.0, "px.joins{dist=broadcast}": 6.0,
               "px.joins{dist=hash}": 2.0,
               "px.exchange_lanes{kind=pkey}": 3000.0,
               "px.exchange_lanes{kind=broadcast}": 900.0,
               "px.exchange_rows{kind=pkey}": 2100.0,
               "px.exchange_bytes{kind=pkey}": 5000.0 + 8e5,
               "px.exchange_bytes{kind=broadcast}": 8e5,
               "px.exchange_overflows{kind=pkey}": 1.0},
           "window": [{"template": "a", "error": None}] * 3
           + [{"template": "b", "error": None}] * 2
           + [{"template": "b", "error": "boom"}],
           "captures": [
               {"template": "a", "executions": 1, "reduced": {"devices": []}},
               {"template": "b", "executions": 2, "reduced": {"devices": []}}]}
    roofline = spec.load_module("layer_metrics", "px_exchange_roofline")
    monkeypatch.setattr(
        roofline.tracing, "xplane_files",
        lambda directory: [os.path.join(directory, "c.xplane.pb")])
    monkeypatch.setattr(roofline.xplane, "load",
                        lambda path: xplane.load_text(_CAPTURE))
    read = lambda name: spec.load_module("layer_metrics", name).compute(rec)
    assert read("px_repartition_join_share") == pytest.approx(100 * 5 / 8)
    assert read("px_exchange_fill_pct") == pytest.approx(
        100 * 2000 / (2000 * 4))
    assert read("px_exchange_overflows") == 1.0
    # 1.6e6 bytes received over the mesh: 3/4 crossed chips, a quarter of
    # that reached each chip; 200 GB/s a chip; the worst device's 40 us of
    # collectives an execution: 3 of a, 2 of b whose capture held two
    least = 1.6e6 * 0.75 / 4 / 200e9
    assert exchange_bytes_model.least_seconds(1.6e6, 4, 1600e9) \
        == pytest.approx(least)
    assert roofline.compute(rec) == pytest.approx(
        100 * least / (3 * 40e-6 + 2 * 40e-6 / 2))
    assert exchange_bytes_model.crossed_bytes_per_chip(1e9, 1) == 0.0
    # a capture that was not reduced, or is not there to read: nothing
    assert roofline.compute(dict(rec, captures=[
        {"template": "a", "executions": 1, "reduced": None}])) is None
    monkeypatch.setattr(roofline.tracing, "xplane_files", lambda d: [])
    assert roofline.compute(rec) is None
    # a program without the counters (the parent's): nothing, no raise
    old = dict(rec, counters_before={}, counters_after={
        "px.joins{dist=broadcast}": 4.0})
    for name in OWN[1:]:
        assert spec.load_module("layer_metrics", name).compute(old) is None
    assert spec.load_module(
        "layer_metrics", OWN[0]).compute(old) == 0.0


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("sf10part4")
    shutil.copytree(spec.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    shutil.copy(os.path.join(spec.REPO_DIR, "BENCHMARK.json"), root)
    return root


ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_DIR,
           XLA_FLAGS="--xla_force_host_platform_device_count=4")


def test_the_cell_rehearses_end_to_end(copy):
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2900000011", "--seconds", "2", "--trace", "1", "--rehearse",
         "0.01"], cwd=copy, env=ENV, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 3, p.stderr[-3000:]
    last = json.loads(p.stdout.splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] >= 4
    assert last["device"]["count"] == 4
    compared = last["compared"]
    assert compared["exact_differ"]["value"] == 0
    assert compared["off_path"]["value"] == 0
    m = last["metrics"]
    for name in ("bind_ms", "host_ms", "stall_s", "load_s",
                 "compiles_in_window", "capacity_retries",
                 "px_repartition_join_share", "px_exchange_overflows"):
        assert name in m, name
    assert m["compiles_in_window"]["value"] == 0
    assert m["capacity_retries"]["value"] == 0
    assert m["px_exchange_overflows"]["value"] == 0


def test_a_lost_row_and_a_float32_sum_both_fail_q9(copy):
    p = subprocess.run(
        [sys.executable, "benchmark/tests/drive_q9_faults.py", CELL,
         "3800000033", "0.01"], cwd=copy, env=ENV, capture_output=True,
        text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads([x for x in p.stdout.splitlines()
                      if x.startswith('{"clean"')][-1])
    assert got["clean"]["failed"] == 0 and got["clean"]["attempted"] >= 4
    assert got["clean"]["pkey_rows"] > 0
    for fault in ("lost_row", "float32"):
        assert got[fault]["checks"]["exact_differ"] > 0, fault
        assert got[fault]["checks"]["raised"] == 0
        assert "tpch_q9_sf10" in got[fault]["templates"], fault
    assert got["float32"]["templates"] == ["tpch_q9_sf10"]
