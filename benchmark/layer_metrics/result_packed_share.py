"""session: of the relations the window brought to the host through
``to_numpy`` (``sql.result_fetches{kind=packed|dense|columns}``: the fetch
picks its regime from the relation's capacity, live count and where its
arrays lie), the share that crossed sized by their rows, all transfers
requested together: packed by the pack program (``packed``) or as they lay
(``dense``), and not column by column (``columns``).  ``None`` when the
window fetched nothing, or the program has no such counter."""

PACKED = "sql.result_fetches{kind=packed}"
DENSE = "sql.result_fetches{kind=dense}"
COLUMNS = "sql.result_fetches{kind=columns}"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    packed, dense, columns = (after.get(k, 0.0) - before.get(k, 0.0)
                              for k in (PACKED, DENSE, COLUMNS))
    if packed + dense + columns <= 0:
        return None
    return 100.0 * (packed + dense) / (packed + dense + columns)
