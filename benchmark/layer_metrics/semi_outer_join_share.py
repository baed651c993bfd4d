"""operators: of the joins the window's statements lowered
(``plan.join_kinds{how=inner|left|semi|anti|full}``: one note a join as
``ops.join`` lowers it, booked at every execution), the share that is not
an inner join: semi-joins (``EXISTS``, ``IN``), anti-joins and outer
joins.  ``None`` when the window ran no join, or the program has no such
counter."""

SERIES = "plan.join_kinds"
NOT_INNER = ("left", "semi", "anti", "full")


def _grew(record, how: str) -> float:
    key = f"{SERIES}{{how={how}}}"
    return record["counters_after"].get(key, 0.0) \
        - record["counters_before"].get(key, 0.0)


def compute(record):
    other = sum(_grew(record, how) for how in NOT_INNER)
    total = other + _grew(record, "inner")
    if total <= 0:
        return None
    return 100.0 * other / total
