"""operators: ``plan.capacity_retries`` over warm-up and window (a retry
re-plans with a larger capacity and compiles again)."""


def compute(record):
    # a counter never bumped is absent from gv$sysstat
    return record["counters_after"].get("plan.capacity_retries", 0.0)
