"""operators: of the reads that the window's sort-path group-bys made of
their lanes in sorted order (``plan.groupby_sorted_reads{kind=sort|gather}``:
one for the live flag and the keys together and one an aggregate's
distinct argument), the share that the group-by's own sort returned (the
keys as its outputs, an argument as a payload operand), with no gather
through the sort's row numbers.  Under 100 where the operator's shape
rule keeps an argument off the sort (few lanes, or a sort already wide)
or a ``count(distinct)`` ran.  ``None`` when the window ran no such
read, or the program has no such counter."""

SORT = "plan.groupby_sorted_reads{kind=sort}"
GATHER = "plan.groupby_sorted_reads{kind=gather}"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    sort = after.get(SORT, 0.0) - before.get(SORT, 0.0)
    gather = after.get(GATHER, 0.0) - before.get(GATHER, 0.0)
    if sort + gather <= 0:
        return None
    return 100.0 * sort / (sort + gather)
