"""``groupby_sorted_read_share`` from a run record's counters: a window
whose sort-path group-bys read their sorted lanes both ways, one way, a
window with none, and a program without the counter (the parent of the PR
that brought it); and the metric's entry in ``BENCHMARK.json``."""

import json
import os

import pytest

from benchmark.harness import spec

SORT = "plan.groupby_sorted_reads{kind=sort}"
GATHER = "plan.groupby_sorted_reads{kind=gather}"


@pytest.mark.parametrize("before, after, want", [
    # a round of heavy: Q3's keys and its one sum's argument, both out of
    # the sort; the warm-up's are not the window's
    ({SORT: 4.0}, {SORT: 152.0}, 100.0),
    # a count(distinct) beside the keys and two arguments: its gathers
    # through its own re-sort are what is left
    ({SORT: 3.0, GATHER: 1.0}, {SORT: 12.0, GATHER: 4.0}, 75.0),
    ({}, {GATHER: 8.0}, 0.0),
    # a window that ran no sort-path group-by (the scan cell, or Q1's
    # masked one alone), and the parent's program
    ({SORT: 2.0}, {SORT: 2.0}, None),
    ({"plan.executions": 3.0, "plan.groupby_segment_reduces{kind=scan}": 2.0},
     {"plan.executions": 9.0, "plan.groupby_segment_reduces{kind=scan}": 8.0},
     None),
])
def test_share_of_the_windows_reads(before, after, want):
    got = spec.load_module(
        "layer_metrics", "groupby_sorted_read_share").compute(
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
                if m["name"] == "groupby_sorted_read_share"]
    # a later PR may append cells to the list, and metrics behind it
    listed = entry.pop("workloads")
    assert entry == {
        "name": "groupby_sorted_read_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "operators",
        "moves": "stmt_geomean_ms"}
    assert {"tpch_sf1.heavy", "tpch_sf1_part4.mix", "tpch_sf10_part4.q9q14",
            "tpch_sf10_orders.q4q13q18"} <= set(listed)
    assert set(listed) <= {w["name"] for w in bench["workloads"]}
    reports = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert reports[entry["moves"]] is None     # every cell reports it
