"""background work (ASH, job and checkpoint threads, the collector, the
machine): the part of the window that the per-template medians do not
explain, ``window - sum over templates of count x median``.  A stalled
statement (4 s in one Q6 and in one Q14 on the chip, PR 23) lands here and
in the rate, not in the medians."""

from benchmark.harness import stats


def compute(record):
    ok = [s for s in record["window"] if s["error"] is None]
    groups = stats.by_template(ok)
    if not groups:
        return None
    return record["window_s"] - sum(
        len(v) * stats.median_low(v) for v in groups.values())
