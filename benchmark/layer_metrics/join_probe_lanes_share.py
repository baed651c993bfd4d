"""operators: of the joins executed in the window that pair probe and build
rows (``plan.join_emits{kind=probe_lanes|expanded}``: the planner marks a
join whose build side is unique on the key by a declared primary key and
whose probe's lanes fit the out capacity, ``HashJoin.build_unique``), the
share that emitted one lane per probe lane instead of expanding into the
out capacity.  ``None`` when the window ran no such join, or the program
has no such counter."""

PROBE_LANES = "plan.join_emits{kind=probe_lanes}"
EXPANDED = "plan.join_emits{kind=expanded}"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    on_probe = after.get(PROBE_LANES, 0.0) - before.get(PROBE_LANES, 0.0)
    expanded = after.get(EXPANDED, 0.0) - before.get(EXPANDED, 0.0)
    if on_probe + expanded <= 0:
        return None
    return 100.0 * on_probe / (on_probe + expanded)
