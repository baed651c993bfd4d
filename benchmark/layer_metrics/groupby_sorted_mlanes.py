"""operators: the lanes the window's sort-path group-bys handed their
sort (``plan.groupby_sort_lanes``: one note a group-by, its input's static
lanes, booked at every execution), in millions a statement of the window.
``None`` when the window ran no statement, or the program has no such
counter."""

SERIES = "plan.groupby_sort_lanes"


def compute(record):
    if SERIES not in record["counters_after"]:
        return None
    statements = sum(1 for s in record["window"] if s["error"] is None)
    if statements == 0:
        return None
    lanes = record["counters_after"][SERIES] \
        - record["counters_before"].get(SERIES, 0.0)
    return lanes / 1e6 / statements
