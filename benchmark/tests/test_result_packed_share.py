"""``result_packed_share`` from a run record's counters: a window of packed
fetches, one of all three kinds, a window that fetched nothing, and a
program without the counter; and the metric's entry in ``BENCHMARK.json``."""

import json
import os

import pytest

from benchmark.harness import spec

PACKED = "sql.result_fetches{kind=packed}"
DENSE = "sql.result_fetches{kind=dense}"
COLUMNS = "sql.result_fetches{kind=columns}"


@pytest.mark.parametrize("before, after, want", [
    # a window of scan: every Q6 packed; the warm-up's are not the window's
    ({PACKED: 40.0}, {PACKED: 9040.0}, 100.0),
    # a DELETE's matched rows crossed as they lay, one relation lay on the
    # host already
    ({PACKED: 10.0, DENSE: 2.0}, {PACKED: 16.0, DENSE: 5.0, COLUMNS: 1.0},
     90.0),
    ({}, {COLUMNS: 4.0}, 0.0),
    ({}, {DENSE: 3.0}, 100.0),
    # a window that fetched nothing, and the parent's program
    ({PACKED: 7.0, COLUMNS: 1.0}, {PACKED: 7.0, COLUMNS: 1.0}, None),
    ({"sql.statements": 3.0}, {"sql.statements": 9.0}, None),
])
def test_share_of_the_windows_fetches(before, after, want):
    got = spec.load_module("layer_metrics", "result_packed_share").compute(
        {"counters_before": before, "counters_after": after})
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_the_entry_in_benchmark_json():
    root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"]
                if m["name"] == "result_packed_share"]
    assert entry == {
        "name": "result_packed_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "session",
        "moves": "stmt_geomean_ms",
        "workloads": ["tpch_sf1.heavy", "tpch_sf1.scan",
                      "tpch_sf1_part4.mix", "tpch_sf1_htap.fresh"]}
    assert set(entry["workloads"]) == {w["name"] for w in bench["workloads"]}
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m is not entry}
