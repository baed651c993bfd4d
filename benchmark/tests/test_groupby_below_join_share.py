"""``groupby_below_join_share`` from a run record's counters: a window
whose group-bys over an outer join's NULL-supplying side went both ways,
one way, a window with none, and a program without the counter (the parent
of the PR that brought it); and the metric's entry in ``BENCHMARK.json``."""

import json
import os

import pytest

from benchmark.harness import spec

BELOW = "plan.groupby_placements{at=below_join}"
ABOVE = "plan.groupby_placements{at=above_join}"


@pytest.mark.parametrize("before, after, want", [
    # the orders cell: Q13's one such group-by a round, pushed; the
    # warm-up's are not the window's
    ({BELOW: 2.0}, {BELOW: 7.0}, 100.0),
    # a statement the rule refuses (count(*)) beside three it takes
    ({BELOW: 1.0, ABOVE: 1.0}, {BELOW: 4.0, ABOVE: 2.0}, 75.0),
    ({}, {ABOVE: 5.0}, 0.0),
    # a window that ran no such group-by, and the parent's program
    ({BELOW: 2.0}, {BELOW: 2.0}, None),
    ({"plan.executions": 3.0, "plan.join_kinds{how=left}": 1.0},
     {"plan.executions": 9.0, "plan.join_kinds{how=left}": 3.0}, None),
])
def test_share_of_the_windows_placements(before, after, want):
    got = spec.load_module(
        "layer_metrics", "groupby_below_join_share").compute(
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
                if m["name"] == "groupby_below_join_share"]
    # a later PR may append cells to the list, and metrics behind it
    listed = entry.pop("workloads")
    assert entry == {
        "name": "groupby_below_join_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "session",
        "moves": "stmt_geomean_ms"}
    assert "tpch_sf10_orders.q4q13q18" in listed
    assert set(listed) <= {w["name"] for w in bench["workloads"]}
    reports = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert reports[entry["moves"]] is None     # every cell reports it
