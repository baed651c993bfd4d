"""operators (the streamed path's chunk program): of the lanes the
statements' plans gave the nodes over the streamed table (every ``Compact``
and join output whose subtree scans it: ``granule.plan_budget_lanes``, summed
over the window's chunk-program executions), the share the chunk programs
were lowered with (``granule.budget_lanes``).  A plan is sized for the whole
table and a granule holds its share of it: 6.25 where Q14's 2,097,152-lane
bucket runs at 131,072 lanes a granule; 100 would mean every granule
compacts and probes at the whole table's capacities.  ``None`` when no such
node ran in the window (Q1 and Q6 have none), or the program has no such
counter."""

LOWERED = "granule.budget_lanes"
PLANNED = "granule.plan_budget_lanes"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    planned = after.get(PLANNED, 0.0) - before.get(PLANNED, 0.0)
    if planned <= 0:
        return None
    return 100.0 * (after.get(LOWERED, 0.0) - before.get(LOWERED, 0.0)) \
        / planned
