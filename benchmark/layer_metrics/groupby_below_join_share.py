"""session (its bind phase): of the window's group-bys that aggregate over
an outer join's NULL-supplying side (``plan.groupby_placements{at=
below_join|above_join}``: one note a group-by), the share the planner put
BELOW the join, over that side's own lanes, where the other way groups the
lanes the join expands into.  100 while Q13's count of ``orders`` runs under
its join on ``customer``; it falls when such a statement keeps its group-by
above the join (an aggregate the rule cannot push, or a join estimated to
emit on fewer lanes than the NULL-supplying side has).  ``None`` when the
window ran no such group-by, or the program has no such counter."""

BELOW = "plan.groupby_placements{at=below_join}"
ABOVE = "plan.groupby_placements{at=above_join}"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    below = after.get(BELOW, 0.0) - before.get(BELOW, 0.0)
    above = after.get(ABOVE, 0.0) - before.get(ABOVE, 0.0)
    if below + above <= 0:
        return None
    return 100.0 * below / (below + above)
