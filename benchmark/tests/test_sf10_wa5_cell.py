"""The cell ``tpch_sf10_wa5.streamed``: its entries in BENCHMARK.json, its
files, the configuration's settings, its seven per-layer readers over a
hand-made record and capture (and over an empty one), and the bytes model
against a hand count.  Membership is asserted, never that an entry or a
list's element is the LAST one: a later cell comes after it."""

import os

import pytest

from benchmark.harness import (granule_bytes_model, granule_spans,
                               program_spans, spec, xplane)

CELL = "tpch_sf10_wa5.streamed"
CONFIG = "tpch_sf10_wa5"
B = spec.read_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))
OWN = ("streamed_share", "granule_fetch_ms", "granule_upload_ms",
       "granule_program_ms", "granule_merge_ms", "granule_upload_gbps",
       "granule_program_roofline")
#: the accepted metrics whose lists took the cell (ISSUE 48, item 7)
APPENDED = ("work_area_resident_share", "bulk_load_us_per_row",
            "analyze_table_s", "hbm_resident_gb", "parse_ms",
            "result_fetch_ms")
#: the accepted metrics with no list: they report on every cell
EVERYWHERE = ("device_idle_pct", "host_ms", "stall_s", "compiles_in_window",
              "capacity_retries", "load_s", "hbm_peak_gb", "bind_ms")


def _reader(name):
    return spec.load_module("layer_metrics", name)


def test_the_cell_and_its_entries():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and not cell.writes()
    assert cell.entry["config"] == CONFIG
    assert cell.entry["traffic"] == "streamed"
    assert [(t["statement"], t["params"])
            for t in cell.traffic["templates"]] == [
        ("tpch_q1_sf10", "validation"), ("tpch_q6", "validation"),
        ("tpch_q14_sf10", "validation")]
    assert cell.traffic["trace_executions"] == 1
    assert cell.tables() == ["lineitem", "part"]
    assert cell.config["dataset"] == {"generator": "tpch_pooled",
                                      "scale": 10.0}
    assert cell.config["system_settings"] == [
        "set global ob_sql_work_area_percentage = 5",
        "set global ob_query_timeout = 36000000000",
        "alter system set temporary_file_max_disk_size = '8G'"]
    assert cell.config["session_settings"] == ["set px_dop = 1"]
    assert cell.config["queries"] == ["Q1", "Q6", "Q14"]
    entry = next(c for c in B["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cell.config["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert len(cell.entry["why"]) <= 200
    assert entry["reduced"] == ["scale_factor", "queries"]
    assert set(entry["reduced"]) == set(cell.config["reduced"])
    assert sum(w["config"] == CONFIG for w in B["workloads"]) == 1
    by_name = {m["name"]: m for m in B["per_layer"]}
    for name in OWN:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "stmt_geomean_ms"
    for name in APPENDED:
        assert CELL in by_name[name]["workloads"], name
    for name in EVERYWHERE:
        assert "workloads" not in by_name[name], name
    listed = {m["name"] for m in B["per_layer"]
              if CELL in m.get("workloads", ())}
    assert listed == set(OWN) | set(APPENDED)
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "stmt_geomean_ms", "setup_s"}
    assert (by_name["granule_program_roofline"]["source"],
            by_name["granule_program_roofline"]["layer"],
            by_name["granule_program_roofline"]["unit"]) == (
        "device_trace", "kernels", "%")
    assert {by_name[n]["layer"] for n in ("granule_fetch_ms",
                                          "granule_upload_ms",
                                          "granule_upload_gbps")} == {
        "storage to device"}
    assert {by_name[n]["layer"] for n in ("granule_program_ms",
                                          "granule_merge_ms")} == {
        "operators"}


def test_the_statements_and_references_are_the_resident_controls():
    mine, control = spec.Cell(CELL), spec.Cell("tpch_sf10.q1q6q14")
    assert mine.statements == control.statements
    assert mine.config["dataset"] == control.config["dataset"]
    assert mine.config["guarantees"]["durability"] == \
        control.config["guarantees"]["durability"]
    assert mine.tables() == control.tables()


# one statement: two granules decoded and copied on the producer's thread,
# two chunk programs and the merge on the statement's; the device busy
# 100 us under the first program, 50 us under the second, 40 us under the
# merge; a terminal (empty) fetch after the last granule
_STREAMED = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 150000000 duration_ps: 100000000 }
    events { metadata_id: 1 offset_ps: 450000000 duration_ps: 50000000 }
    events { metadata_id: 1 offset_ps: 720000000 duration_ps: 40000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = u32[8] fusion()" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000000 }
    events { metadata_id: 2 offset_ps: 100000000 duration_ps: 200000000 }
    events { metadata_id: 2 offset_ps: 400000000 duration_ps: 200000000 }
    events { metadata_id: 3 offset_ps: 700000000 duration_ps: 100000000 } }
  lines { id: 2 name: "granule-prefetch" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 89000000 }
    events { metadata_id: 5 offset_ps: 90000000 duration_ps: 50000000 }
    events { metadata_id: 4 offset_ps: 150000000 duration_ps: 200000000 }
    events { metadata_id: 5 offset_ps: 350000000 duration_ps: 40000000 }
    events { metadata_id: 4 offset_ps: 600000000 duration_ps: 11000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:execute:q" } }
  event_metadata { key: 2 value { id: 2 name: "ob:granule.program" } }
  event_metadata { key: 3 value { id: 3 name: "ob:granule.merge" } }
  event_metadata { key: 4 value { id: 4 name: "ob:granule.fetch" } }
  event_metadata { key: 5 value { id: 5 name: "ob:granule.upload" } } }
"""
_NOT_STREAMED = _STREAMED.replace("ob:granule.", "ob:other.")

_LAYOUTS = {
    "lineitem": {"capacity": 1000, "mask_itemsize": 1, "columns": {
        "a": {"itemsize": 8, "valid_itemsize": 0},
        "b": {"itemsize": 4, "valid_itemsize": 0},
        "unread": {"itemsize": 8, "valid_itemsize": 0}}},
    "part": {"capacity": 100, "mask_itemsize": 1, "columns": {
        "p": {"itemsize": 8, "valid_itemsize": 1}}}}
_READS = {"lineitem": ["a", "b"], "part": ["p"]}


def _record(tmp_path, monkeypatch, pbtxt=_STREAMED):
    from jax.profiler import ProfileData

    monkeypatch.setattr(spec, "SCRATCH_DIR", str(tmp_path))
    monkeypatch.setattr(program_spans, "_by_cell", {})
    monkeypatch.setattr(granule_spans, "_by_cell", {})
    d = tmp_path / "trace" / CELL / "q" / "plugins" / "profile" / "x"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(pbtxt))
    return {
        "cell": {"name": CELL},
        "captures": [{"template": "q", "executions": 1, "reduced": {}}],
        "device": {"kind": "TPU v5 lite", "count": 1},
        "statements": {"q": {"reads": _READS}}, "layouts": _LAYOUTS,
        "window": [{"template": "q", "error": None}] * 3
        + [{"template": "q", "error": "boom"}],
        "plan_traces_after": {
            "h1": {"xla_trace_count": 1,
                   "plan_text": "granule(lanes=100) ScalarAgg(...)"},
            "h2": {"xla_trace_count": 1, "plan_text": "ScalarAgg(...)"}},
        "counters_before": {"granule.upload_bytes": 1000.0,
                            "sql.work_area_decisions{kind=spill}": 2.0,
                            "spill.executions{kind=scalar}": 2.0},
        "counters_after": {"granule.upload_bytes": 10000.0,
                           "sql.work_area_decisions{kind=spill}": 6.0,
                           "spill.executions{kind=scalar}": 3.0,
                           "spill.executions{kind=groupby}": 2.0,
                           "spill.fallbacks{reason=x}": 1.0}}


def _empty():
    return {"cell": {"name": "no.such.cell"}, "captures": [],
            "device": {"kind": "TPU v5 lite", "count": 1},
            "statements": {}, "layouts": {}, "window": [],
            "plan_traces_after": {}, "counters_before": {},
            "counters_after": {}}


def test_the_span_reader_takes_every_host_thread(tmp_path, monkeypatch):
    _record(tmp_path, monkeypatch)
    files = [os.path.join(r, f) for r, _d, fs in os.walk(tmp_path)
             for f in fs]
    (st,) = granule_spans.reduce_profile(xplane.load(files[0]))
    assert st["count"] == {"fetch": 3, "upload": 2, "program": 2,
                           "merge": 1}
    assert st["seconds"]["fetch"] == pytest.approx(300e-6)
    assert st["seconds"]["upload"] == pytest.approx(90e-6)
    assert st["seconds"]["program"] == pytest.approx(400e-6)
    assert st["seconds"]["merge"] == pytest.approx(100e-6)
    # busy under the two programs, not under the merge
    assert st["program_busy_s"] == pytest.approx(150e-6)


@pytest.mark.parametrize("name,want", [
    ("streamed_share", 100.0 * 3 / 4),
    ("granule_fetch_ms", 0.3), ("granule_upload_ms", 0.09),
    ("granule_program_ms", 0.4), ("granule_merge_ms", 0.1),
    # 9,000 B over 3 window executions x 90 us of upload spans
    ("granule_upload_gbps", 9000e-9 / (3 * 90e-6)),
    # two programs x (100 lanes x 13 B of lineitem + 100 x 10 B of part)
    ("granule_program_roofline", 100.0 * (2 * 2300 / 819e9) / 150e-6),
])
def test_each_reader_on_a_hand_made_record(tmp_path, monkeypatch, name,
                                           want):
    record = _record(tmp_path, monkeypatch)
    assert _reader(name).compute(record) == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", OWN)
def test_each_reader_finds_nothing_in_an_empty_record(tmp_path, monkeypatch,
                                                      name):
    monkeypatch.setattr(granule_spans, "_by_cell", {})
    assert _reader(name).compute(_empty()) is None
    # a capture of a program that writes no granule span (the parent's)
    record = _record(tmp_path, monkeypatch, _NOT_STREAMED)
    record["counters_after"] = dict(record["counters_before"])
    record["plan_traces_after"].pop("h1")
    assert _reader(name).compute(record) is None


def test_the_bytes_model_against_a_hand_count():
    m = granule_bytes_model
    assert m.granule_lanes(["granule(lanes=2097152) GroupBy(...)",
                            "GroupBy(...)", "result.count_body()"]) \
        == 2097152
    assert m.granule_lanes(["GroupBy(...)"]) is None
    assert m.granule_lanes(["granule(lanes=64) A", "granule(lanes=128) B"]) \
        is None
    assert m.lane_bytes(_LAYOUTS["lineitem"], ["a", "b"]) == 13
    assert m.lane_bytes(_LAYOUTS["part"], ["p"]) == 10
    # lineitem streams (1000 lanes over a granule's 100), part is resident
    assert m.program_bytes(_READS, _LAYOUTS, 100) == 100 * 13 + 100 * 10
    # a granule as large as the table: both whole
    assert m.program_bytes(_READS, _LAYOUTS, 1000) == 1000 * 13 + 100 * 10
    assert m.least_seconds(_READS, _LAYOUTS, 100, 29, 819e9) == \
        pytest.approx(29 * 2300 / 819e9)
    # the cell's own statements at its granule: Q1 reads 7 columns
    cell = spec.Cell(CELL)
    lay = {"lineitem": {"capacity": 67108864, "mask_itemsize": 1,
                        "columns": {c: {"itemsize": 4 if c in (
                            "l_returnflag", "l_linestatus", "l_shipdate")
                            else 8, "valid_itemsize": 0}
                            for c in cell.reads()["lineitem"]}}}
    q1 = cell.statements["tpch_q1_sf10"]["reads"]
    assert m.program_bytes(q1, lay, 2097152) == 2097152 * (4 * 8 + 3 * 4 + 1)
