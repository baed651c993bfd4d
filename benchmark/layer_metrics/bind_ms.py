"""session layer: median ``gv$sql_audit.bind_s`` (parse to plan, plan-cache
probe included) of the window's statements."""

from benchmark.harness import stats


def compute(record):
    xs = [s["audit"]["bind_s"] for s in record["window"] if s.get("audit")]
    return 1e3 * stats.median(xs) if xs else None
