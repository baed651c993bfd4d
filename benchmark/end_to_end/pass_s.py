"""Sum over templates of the median client latency: one pass through the
mix (TPC-H Throughput's arithmetic: the slow statements count most)."""

from benchmark.harness import stats


def compute(record):
    medians = stats.window_medians(record)
    return sum(medians.values()) if medians else None
