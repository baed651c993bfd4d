"""``join_probe_lanes_share`` from a run record's counters: a window whose
joins emitted both ways, one way, a window with no join, and a program
without the counter (the parent of the PR that brought it); and the
metric's entry in ``BENCHMARK.json``."""

import json
import os

import pytest

from benchmark.harness import spec

PROBE_LANES = "plan.join_emits{kind=probe_lanes}"
EXPANDED = "plan.join_emits{kind=expanded}"


@pytest.mark.parametrize("before, after, want", [
    # a round of heavy: Q14's join on its probe's lanes, Q3's two expand;
    # the warm-up's are not the window's
    ({PROBE_LANES: 2.0, EXPANDED: 4.0}, {PROBE_LANES: 37.0, EXPANDED: 74.0},
     100.0 / 3),
    # tpch_sf10.q1q6q14: Q14's is the only join
    ({PROBE_LANES: 3.0}, {PROBE_LANES: 61.0}, 100.0),
    # part4.mix: PX Q3's probes are wider than their budgets
    ({}, {EXPANDED: 8.0}, 0.0),
    # a window that ran no join (the scan cell), and the parent's program
    ({PROBE_LANES: 2.0, EXPANDED: 2.0}, {PROBE_LANES: 2.0, EXPANDED: 2.0},
     None),
    ({"plan.executions": 3.0, "plan.join_inputs{kind=whole}": 2.0},
     {"plan.executions": 9.0, "plan.join_inputs{kind=whole}": 8.0}, None),
])
def test_share_of_the_windows_joins(before, after, want):
    got = spec.load_module(
        "layer_metrics", "join_probe_lanes_share").compute(
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
                if m["name"] == "join_probe_lanes_share"]
    # a later PR may append cells to the list, and metrics behind it
    listed = entry.pop("workloads")
    assert entry == {
        "name": "join_probe_lanes_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "operators",
        "moves": "stmt_geomean_ms"}
    assert {"tpch_sf1.heavy", "tpch_sf1_part4.mix",
            "tpch_sf10.q1q6q14"} <= set(listed)
    assert set(listed) <= {w["name"] for w in bench["workloads"]}
    reports = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert reports[entry["moves"]] is None     # every cell reports it
