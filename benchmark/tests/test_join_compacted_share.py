"""``join_compacted_share`` from a run record's counters: a window whose
joins took inputs of both kinds, of one kind, a window with no join, and a
program without the counter; and the metric's entry in ``BENCHMARK.json``."""

import json
import os

import pytest

from benchmark.harness import spec

COMPACTED = "plan.join_inputs{kind=compacted}"
WHOLE = "plan.join_inputs{kind=whole}"


@pytest.mark.parametrize("before, after, want", [
    # a round of heavy: Q14's join (one input compacted, one whole) and
    # Q3's two joins (four whole); the warm-up's are not the window's
    ({COMPACTED: 2.0, WHOLE: 10.0}, {COMPACTED: 37.0, WHOLE: 185.0},
     100.0 / 6),
    ({WHOLE: 4.0}, {COMPACTED: 3.0, WHOLE: 5.0}, 75.0),
    ({}, {WHOLE: 8.0}, 0.0),
    ({}, {COMPACTED: 2.0}, 100.0),
    # a window that ran no join (the scan cell), and the parent's program
    ({COMPACTED: 2.0, WHOLE: 2.0}, {COMPACTED: 2.0, WHOLE: 2.0}, None),
    ({"plan.executions": 3.0}, {"plan.executions": 9.0}, None),
])
def test_share_of_the_windows_join_inputs(before, after, want):
    got = spec.load_module("layer_metrics", "join_compacted_share").compute(
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
                if m["name"] == "join_compacted_share"]
    assert entry == {
        "name": "join_compacted_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "operators",
        "moves": "stmt_geomean_ms",
        "workloads": ["tpch_sf1.heavy", "tpch_sf1_part4.mix"]}
    assert bench["per_layer"][-1] is entry  # appended, nothing moved
    cells = {w["name"] for w in bench["workloads"]}
    assert set(entry["workloads"]) <= cells
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"][:-1]}
