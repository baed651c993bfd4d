"""operators: how much of the sort-path group-bys' static output lanes is
groups: ``plan.groupby_groups`` (the live groups each found, a traced
scalar read with the overflow lanes) over ``plan.groupby_out_lanes`` (the
lanes each emits on) in the window.  ``None`` when the window ran no
sort-path group-by, or the program has no such counters."""

GROUPS = "plan.groupby_groups"
LANES = "plan.groupby_out_lanes"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    if GROUPS not in after:
        return None
    lanes = after.get(LANES, 0.0) - before.get(LANES, 0.0)
    if lanes <= 0:
        return None
    return 100.0 * (after[GROUPS] - before.get(GROUPS, 0.0)) / lanes
