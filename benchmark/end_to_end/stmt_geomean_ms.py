"""Geometric mean, over the cell's statement templates, of each template's
median client latency in the window (TPC-H Power's arithmetic: every
template counts alike)."""

from benchmark.harness import stats


def compute(record):
    medians = stats.window_medians(record)
    return 1e3 * stats.geomean(medians.values()) if medians else None
