"""The HTAP cell ``tpch_sf1_htap.fresh``: its files resolve, it rehearses end
to end on the CPU, a program that skips one committed delta is found by the
replayed reference, and its three per-layer readers read what they say."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.harness import delta_bytes_model, program_spans, spec, xplane

CELL = "tpch_sf1_htap.fresh"
B = spec.read_json(os.path.join(spec.REPO_DIR, "BENCHMARK.json"))


def _reader(name):
    return spec.load_module("layer_metrics", name)


@pytest.fixture()
def copy_of_the_benchmark(tmp_path):
    """The benchmark's files in a directory of the test's own: a run keeps
    its database under ``benchmark/.scratch`` of the tree it runs from, and
    two tests must not share one."""
    shutil.copytree(spec.BENCH_DIR, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".scratch", "__pycache__"))
    shutil.copy(os.path.join(spec.REPO_DIR, "BENCHMARK.json"), tmp_path)
    return tmp_path


def _run(root, script, *args, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.REPO_DIR)
    p = subprocess.run([sys.executable, script, *args], cwd=root,
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    lines = [json.loads(x) for x in p.stdout.splitlines()
             if x.startswith("{")]
    return p.returncode, lines, p.stderr


def test_the_cell_resolves_to_its_files():
    cell = spec.Cell(CELL)
    assert cell.chips == 1 and cell.writes()
    assert [t["statement"] for t in cell.traffic["templates"]] == \
        ["tpch_rf1", "tpch_q6", "tpch_rf2"]
    assert all(int(t["every"]) == 1 for t in cell.traffic["templates"])
    assert cell.tables() == ["orders", "lineitem"]
    assert cell.read_back() == ["orders", "lineitem"]
    ddl = [s for s in cell.config["system_settings"]
           if s.startswith("create table")]
    assert len(ddl) == 2 and all(
        s.endswith("with column group (all columns, each column)")
        and "partition" not in s for s in ddl)
    mine = [m["name"] for m in B["per_layer"]
            if m.get("workloads") == [CELL]]
    assert mine == ["delta_apply_ms", "delta_apply_share",
                    "delta_apply_roofline"]
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert e2e == {"stmt_geomean_ms", "setup_s"}


def test_the_guarantees_are_the_refresh_configurations_word_for_word():
    rf = spec.read_json(os.path.join(spec.BENCH_DIR, "configs",
                                     "tpch_sf1_rf.json"))
    mine = spec.Cell(CELL).config
    assert mine["guarantees"] == rf["guarantees"]
    assert mine["dataset"] == rf["dataset"]
    assert mine["session_settings"] == rf["session_settings"]
    assert mine["system_settings"][:1] == rf["system_settings"]
    assert mine["queries_between_refreshes"] == 1


def test_the_round_is_rf1_the_read_rf2():
    from benchmark.harness import traffic

    cell = spec.Cell(CELL)
    round_ = traffic.schedule(cell.traffic, cell.statements, seed=5)
    assert [it.template for it in round_[:6]] == \
        ["tpch_rf1", "tpch_q6", "tpch_rf2"] * 2
    assert len(round_) == 48
    assert len({it.key for it in round_ if it.sql}) == 16
    # the same 16 parameter sets as the scan cell's: one set of plans
    scan = spec.Cell("tpch_sf1.scan")
    assert {it.key for it in round_ if it.sql} == {
        it.key for it in traffic.schedule(scan.traffic, scan.statements,
                                          seed=5)}


def test_the_cell_rehearses_with_exit_code_3(copy_of_the_benchmark):
    rc, lines, err = _run(copy_of_the_benchmark, "benchmark/run.py", "--workload", CELL, "--seed",
                          "1", "--seconds", "5", "--rehearse", "0.01")
    assert rc == 3, err[-3000:]
    last = lines[-1]
    assert last["rehearsal"] is True and last["correct"] is False
    assert last["failed"] == 0 and last["attempted"] >= 5
    assert set(last["metrics"]) == {"stmt_geomean_ms", "setup_s"}
    assert all(c["value"] <= c["limit"] for c in last["compared"].values())


def test_a_program_that_skips_one_delta_is_found_by_the_reference(
        copy_of_the_benchmark):
    rc, lines, err = _run(copy_of_the_benchmark,
                          "benchmark/tests/drive_skipped_delta.py", CELL,
                          "7", "0.05")
    assert rc == 0, err[-3000:]
    got = lines[-1]
    assert got["clean"]["failed"] == 0 and got["clean"]["attempted"] >= 6
    assert got["clean"]["checks"]["exact_differ"] == 0
    assert got["skipped"]["skipped_rows"][0] > 0
    assert got["skipped"]["checks"]["exact_differ"] > 0
    assert got["skipped"]["checks"]["raised"] == 0


# -- the readers, on records made by hand ---------------------------------------

def test_delta_apply_share():
    compute = _reader("delta_apply_share").compute
    rec = {"counters_before": {"storage.delta_applies": 4.0,
                               "storage.device_copy_builds": 2.0},
           "counters_after": {"storage.delta_applies": 13.0,
                              "storage.device_copy_builds": 2.0}}
    assert compute(rec) == 100.0
    rec["counters_after"]["storage.device_copy_builds"] = 5.0
    assert compute(rec) == pytest.approx(75.0)
    # a window without a commit; a program without the counter
    assert compute({"counters_before": {"storage.delta_applies": 1.0},
                    "counters_after": {"storage.delta_applies": 1.0}}) is None
    assert compute({"counters_before": {}, "counters_after": {
        "storage.device_copy_builds": 3.0}}) is None


def test_delta_apply_ms(monkeypatch):
    compute = _reader("delta_apply_ms").compute

    def reds(*self_ns):
        return {"q": {"statements": [{"self_ns": s} for s in self_ns]}}

    monkeypatch.setattr(program_spans, "load", lambda record: reds(
        {"tables": 1e5, "storage.delta_apply": 30e6,
         "storage.delta_read": 20e6},
        {"tables": 1e5},                       # found its copy current
        {"storage.delta_apply": 10e6, "storage.delta_read": 5e6}))
    assert compute({}) == pytest.approx((50.0 + 15.0) / 2)
    monkeypatch.setattr(program_spans, "load",
                        lambda record: reds({"tables": 1e5}))
    assert compute({}) is None                 # the parent: no such span
    monkeypatch.setattr(program_spans, "load", lambda record: None)
    assert compute({}) is None


_CAPTURE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 20000000 duration_ps: 30000000 }
    events { metadata_id: 1 offset_ps: 70000000 duration_ps: 20000000 }
    events { metadata_id: 1 offset_ps: 150000000 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "%scatter.1 = s64[64] scatter()" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 200000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 70000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:execute:tpch_q6" } }
  event_metadata { key: 2 value { id: 2 name: "ob:storage.delta_apply" } } }
"""

_LAYOUT = {"capacity": 64, "mask_itemsize": 1, "columns": {
    "l_orderkey": {"itemsize": 8, "valid_itemsize": 0},
    "l_comment": {"itemsize": 4, "valid_itemsize": 1}}}


def test_busy_under_a_program_span():
    reader = _reader("delta_apply_roofline")
    sec, n = reader.busy_under(xplane.load_text(_CAPTURE), reader.SPAN)
    # the span covers 10-80 us: the ops at 20-50 and 70-80 (clipped)
    assert n == 1 and sec == pytest.approx(40e-6)
    assert reader.busy_under(xplane.load_text(_CAPTURE), "ob:none") == \
        (0.0, 0)


def test_the_deltas_own_bytes():
    assert delta_bytes_model.lane_bytes(_LAYOUT) == 1 + 8 + 4 + 1
    assert delta_bytes_model.delta_bytes(_LAYOUT, 10, 7) == 10 * 14 + 7
    sent = [{"k": 0, "template": "w"}, {"template": "r"},
            {"k": 1, "template": "w"}, {"k": 0, "template": "x"},
            {"template": "r"}]
    assert delta_bytes_model.committed_between(sent, 4) == sent[2:4]
    assert delta_bytes_model.committed_between(sent, 1) == sent[:1]
    assert delta_bytes_model.committed_between(sent, 0) == []


class _Dataset:
    @staticmethod
    def new(scale, seed, k, batch):
        return [{"o": {"ok": list(range(batch))},
                 "l": {"lk": list(range(3 * batch))}}] * 2

    @staticmethod
    def old(scale, seed, k, batch):
        return [{"o": {"ok": list(range(batch))}}] * 3


_FILES = {
    "w_new": {"rows": "new", "batch": 4, "transaction": [
        {"insert": "orders", "rows": "o"},
        {"insert": "lineitem", "rows": "l"}]},
    "w_old": {"rows": "old", "batch": 4, "transaction": [
        {"delete": "lineitem", "where": "lk", "rows": "o", "column": "ok"},
        {"delete": "orders", "where": "ok", "rows": "o", "column": "ok"}]}}


def test_rows_written_and_lanes_cleared_come_from_the_row_sets():
    writes = [{"template": "w_new", "k": 0, "acks": [True, False]},
              {"template": "w_old", "k": 0, "acks": [True, True, True]}]
    assert delta_bytes_model.table_delta(
        writes, _FILES, _Dataset, 1.0, 1, "lineitem") == (12, 12)
    assert delta_bytes_model.table_delta(
        writes, _FILES, _Dataset, 1.0, 1, "orders") == (4, 12)


@pytest.mark.parametrize("busy_us,want", [(40.0, None), (0.0, None)])
def test_delta_apply_roofline_on_a_record(monkeypatch, tmp_path, busy_us,
                                          want):
    reader = _reader("delta_apply_roofline")
    path = tmp_path / "c.xplane.pb"
    path.write_bytes(b"")
    monkeypatch.setattr(reader.tracing, "xplane_files",
                        lambda directory: [str(path)])
    text = _CAPTURE if busy_us else _CAPTURE.replace(
        "ob:storage.delta_apply", "ob:tables")
    monkeypatch.setattr(reader.xplane, "load",
                        lambda p: xplane.load_text(text))
    monkeypatch.setattr(reader.spec, "load_module",
                        lambda kind, name: _Dataset)
    layout = dict(_LAYOUT)
    record = {
        "device": {"kind": "TPU v5 lite"}, "scale": 1.0, "seed": 1,
        "cell": {"name": CELL},
        "config": {"dataset": {"generator": "x"}},
        "statements": dict(_FILES, tpch_q6={"reads": {"lineitem": ["a"]}}),
        "layouts": {"lineitem": layout},
        "captures": [{"template": "w_new", "reduced": {}},
                     {"template": "tpch_q6", "reduced": {}}],
        "window": [{"phase": "window", "template": "tpch_q6"},
                   {"phase": "window", "template": "w_old", "k": 0,
                    "acks": [True, True, True]}],
        "traced": [{"phase": "trace", "template": "w_new", "k": 1,
                    "acks": [True, True]},
                   {"phase": "trace", "template": "tpch_q6"},
                   {"phase": "trace", "template": "tpch_q6"}]}
    got = reader.compute(record)
    if not busy_us:
        assert got is None      # no traced read applied a delta
        return
    # 24 rows written and 12 lanes cleared at least, 14 B a lane, over
    # 819 GB/s, against 40 us busy
    least = (24 * 14 + 12) / 819e9
    assert got == pytest.approx(100.0 * least / 40e-6)
    assert 0 < got < 100.0
    # whatever the delta, the share cannot pass 100: the bytes are the
    # delta's own and the time is the device's under the apply
    layout["columns"] = {f"c{i}": {"itemsize": 8, "valid_itemsize": 1}
                         for i in range(16)}
    assert reader.compute(record) < 100.0
