"""operators: of the group-bys executed in the window
(``plan.groupby_reduces{kind=masked|sort}``: the program picks a group-by's
way from its keys' static types and code space), the share that grouped by
dictionary codes with masked streaming reductions, with no sort and no
scatter.  ``None`` when the window ran no group-by, or the program has no
such counter."""

MASKED = "plan.groupby_reduces{kind=masked}"
SORT = "plan.groupby_reduces{kind=sort}"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    masked = after.get(MASKED, 0.0) - before.get(MASKED, 0.0)
    sort = after.get(SORT, 0.0) - before.get(SORT, 0.0)
    if masked + sort <= 0:
        return None
    return 100.0 * masked / (masked + sort)
