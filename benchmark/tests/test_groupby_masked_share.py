"""``groupby_masked_share`` from a run record's counters: a window with
group-bys of both kinds, of one kind, with none, and a program without the
counter."""

import pytest

from benchmark.harness import spec

MASKED = "plan.groupby_reduces{kind=masked}"
SORT = "plan.groupby_reduces{kind=sort}"


@pytest.mark.parametrize("before, after, want", [
    # the warm-up's group-bys are not the window's
    ({MASKED: 2.0}, {MASKED: 11.0}, 100.0),
    ({MASKED: 2.0, SORT: 1.0}, {MASKED: 5.0, SORT: 2.0}, 75.0),
    ({}, {SORT: 4.0}, 0.0),
    # a window that ran no group-by (the scan cell), and
    # the parent's program
    ({MASKED: 2.0}, {MASKED: 2.0}, None),
    ({"plan.executions": 3.0}, {"plan.executions": 9.0}, None),
])
def test_share_of_the_windows_groupbys(before, after, want):
    got = spec.load_module("layer_metrics", "groupby_masked_share").compute(
        {"counters_before": before, "counters_after": after})
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
