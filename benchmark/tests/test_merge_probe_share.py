"""``merge_probe_share`` from a run record's counters: a window with probes
of both kinds, of one kind, with none, and a program without the counter."""

import pytest

from benchmark.harness import spec

MERGE = "plan.join_probes{kind=merge}"
SEARCH = "plan.join_probes{kind=search}"


@pytest.mark.parametrize("before, after, want", [
    # warm-up probes are not the window's
    ({MERGE: 6.0}, {MERGE: 12.0}, 100.0),
    ({MERGE: 6.0, SEARCH: 1.0}, {MERGE: 9.0, SEARCH: 2.0}, 75.0),
    ({}, {SEARCH: 4.0}, 0.0),
    # a window that ran no probe (the scan cell), and the parent's program
    ({MERGE: 6.0}, {MERGE: 6.0}, None),
    ({"plan.executions": 3.0}, {"plan.executions": 9.0}, None),
])
def test_share_of_the_windows_probes(before, after, want):
    got = spec.load_module("layer_metrics", "merge_probe_share").compute(
        {"counters_before": before, "counters_after": after})
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
