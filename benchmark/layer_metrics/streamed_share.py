"""session: of the window's statements the work-area budget priced over
itself (``sql.work_area_decisions{kind=spill}``), the share that ran through
the streaming tier (``spill.executions`` of every kind) and not, after a
fall-back, as the resident plan the budget refused.  100 where every such
statement streams.  ``None`` when the window priced nothing over the
budget, or the program has no such counter."""

PRICED = "sql.work_area_decisions{kind=spill}"
RAN = "spill.executions"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    priced = after.get(PRICED, 0.0) - before.get(PRICED, 0.0)
    if priced <= 0:
        return None
    ran = sum(v - before.get(k, 0.0) for k, v in after.items()
              if k.split("{")[0] == RAN)
    return 100.0 * ran / priced
