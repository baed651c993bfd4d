"""operators: of the join and index probes executed in the window
(``plan.join_probes{kind=merge|search}``: the program picks a probe's kind
from its static shapes), the share that ranked their keys by merging.
``None`` when the window ran no probe, or the program has no such counter."""

MERGE = "plan.join_probes{kind=merge}"
SEARCH = "plan.join_probes{kind=search}"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    merge = after.get(MERGE, 0.0) - before.get(MERGE, 0.0)
    search = after.get(SEARCH, 0.0) - before.get(SEARCH, 0.0)
    if merge + search <= 0:
        return None
    return 100.0 * merge / (merge + search)
