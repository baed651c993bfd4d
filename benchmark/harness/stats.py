"""The arithmetic of the metrics: medians, percentiles, geometric mean.

Plain Python on lists of floats; no reading is rounded."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100), linear between order statistics (the
    same rule as ``numpy.percentile``'s default)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def median_low(values) -> float:
    """The median as an order statistic: for an even count the LOWER of the
    two middle readings.  A template answered twice in a window has no
    middle reading, and one stalled answer must not move its number."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("median of nothing")
    return xs[(len(xs) - 1) // 2]


def geomean(values) -> float:
    xs = [float(v) for v in values]
    if not xs or min(xs) <= 0.0:
        raise ValueError("geometric mean needs positive values")
    return math.exp(sum(math.log(x) for x in xs) / len(xs))


def by_template(samples, key="template", value="latency_s") -> dict:
    """{template: [values]} in first-seen order."""
    out: dict[str, list] = {}
    for s in samples:
        if s.get(value) is not None:
            out.setdefault(s[key], []).append(s[value])
    return out


def template_medians(samples, value="latency_s") -> dict:
    return {t: median_low(v)
            for t, v in by_template(samples, value=value).items()}


def window_medians(record) -> dict | None:
    """Per-template medians of the window's answered statements; ``None``
    when a template of the cell never answered (no aggregate over a mix
    with a hole in it)."""
    medians = template_medians(
        [s for s in record["window"] if s["error"] is None])
    return medians if len(medians) == len(record["templates"]) else None


def spread(values) -> float:
    """The distance between the quartiles over the median: how the driver
    reads the spread of a set of runs."""
    m = median(values)
    return (percentile(values, 75.0) - percentile(values, 25.0)) / m
