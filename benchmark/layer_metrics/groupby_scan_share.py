"""operators: of the reductions that the window's sort-path group-bys ran
over their sorted lanes (``plan.groupby_segment_reduces{kind=scan|scatter}``:
one for the groups' own lanes and one an aggregate, the way picked from
the aggregate's function and its argument's type), the share computed by
prefix sums or a segmented scan read at the groups' end lanes, with no
scatter over the group number.  ``None`` when the window ran no such
reduction, or the program has no such counter."""

SCAN = "plan.groupby_segment_reduces{kind=scan}"
SCATTER = "plan.groupby_segment_reduces{kind=scatter}"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    scan = after.get(SCAN, 0.0) - before.get(SCAN, 0.0)
    scatter = after.get(SCATTER, 0.0) - before.get(SCATTER, 0.0)
    if scan + scatter <= 0:
        return None
    return 100.0 * scan / (scan + scatter)
