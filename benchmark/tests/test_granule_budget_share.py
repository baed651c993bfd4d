"""``granule_budget_share`` from a run record's counters: a window whose
chunk programs ran at a granule's budget, at the plan's own, a window in
which no budgeted node ran, and a program without the counters (the parent
of the PR that brought them); and the metric's entry in
``BENCHMARK.json``."""

import json
import os

import pytest

from benchmark.harness import spec

LOWERED = "granule.budget_lanes"
PLANNED = "granule.plan_budget_lanes"


@pytest.mark.parametrize("before, after, want", [
    # the streamed cell: Q14's Compact and join, 2 x 2,097,152 lanes in the
    # plan and 2 x 131,072 in the program, 29 programs a round, five
    # rounds; the warm-up's two are not the window's
    ({LOWERED: 2 * 29 * 262144.0, PLANNED: 2 * 29 * 4194304.0},
     {LOWERED: 7 * 29 * 262144.0, PLANNED: 7 * 29 * 4194304.0}, 6.25),
    # a scan without an estimate beside one with: one program at the
    # plan's capacities, one at a quarter
    ({}, {LOWERED: 4096.0 + 1024.0, PLANNED: 2 * 4096.0}, 62.5),
    # the rule never engaged
    ({LOWERED: 8.0, PLANNED: 8.0}, {LOWERED: 72.0, PLANNED: 72.0}, 100.0),
    # a window of Q1 and Q6 alone, an empty record, the parent's program
    ({LOWERED: 5.0, PLANNED: 80.0, "granule.count": 58.0},
     {LOWERED: 5.0, PLANNED: 80.0, "granule.count": 116.0}, None),
    ({}, {}, None),
    ({"granule.count": 58.0, "granule.rows": 1.2e8},
     {"granule.count": 203.0, "granule.rows": 4.2e8}, None),
])
def test_share_of_the_plans_lanes_the_programs_ran_at(before, after, want):
    got = spec.load_module(
        "layer_metrics", "granule_budget_share").compute(
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
                if m["name"] == "granule_budget_share"]
    # a later PR may append cells to the list, and metrics behind it
    listed = entry.pop("workloads")
    assert entry == {
        "name": "granule_budget_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "operators",
        "moves": "stmt_geomean_ms"}
    assert "tpch_sf10_wa5.streamed" in listed
    assert set(listed) <= {w["name"] for w in bench["workloads"]}
    reports = {m["name"]: m.get("workloads") for m in bench["end_to_end"]}
    assert reports[entry["moves"]] is None     # every cell reports it
