"""The arithmetic of the metrics: percentile, median, geomean, the two
aggregates over per-template medians, and the spread as the driver reads
it."""

import math

import numpy as np
import pytest

from benchmark.harness import spec, stats


@pytest.mark.parametrize("values,q", [
    ([1.0], 50), ([1.0, 2.0], 50), ([3.0, 1.0, 2.0], 50),
    ([5.0, 1.0, 9.0, 3.0], 25), ([5.0, 1.0, 9.0, 3.0], 75),
    (list(range(1, 1001)), 99), (list(range(1, 1001)), 99.9),
    ([0.5, 0.25, 8.0, 2.0, 2.0], 95),
])
def test_percentile_is_numpys(values, q):
    assert stats.percentile(values, q) == pytest.approx(
        float(np.percentile(values, q)), rel=1e-12)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


@pytest.mark.parametrize("values,want", [
    ([5.0], 5.0), ([9.3, 13.3], 9.3), ([3.0, 1.0, 2.0], 2.0),
    ([4.0, 1.0, 3.0, 2.0], 2.0), ([1.0, 1.0, 50.0, 1.0, 1.0], 1.0),
])
def test_median_low_is_an_order_statistic(values, want):
    assert stats.median_low(values) == want
    assert stats.median_low(values) in values
    assert stats.median_low(values) <= stats.median(values)


def test_geomean():
    assert stats.geomean([2.0, 8.0]) == pytest.approx(4.0)
    assert stats.geomean([3.6, 9.3, 13.0]) == pytest.approx(
        (3.6 * 9.3 * 13.0) ** (1 / 3))
    with pytest.raises(ValueError):
        stats.geomean([1.0, 0.0])


def _record(latencies: dict) -> dict:
    window = [{"template": t, "latency_s": x, "error": None, "correct": True}
              for t, xs in latencies.items() for x in xs]
    return {"window": window, "templates": list(latencies), "window_s": 50.0,
            "setup_seconds": 12.5}


def _e2e(name):
    return spec.load_module("end_to_end", name).compute


def test_aggregates_come_from_per_template_medians():
    # the window cut the round: a has 3 samples, b and c have 2
    # b was stalled once: with two readings the LOWER one is its median
    rec = _record({"a": [1.0, 3.0, 2.0], "b": [8.0, 12.0], "c": [4.0, 4.0]})
    medians = [2.0, 8.0, 4.0]
    assert _e2e("pass_s")(rec) == pytest.approx(sum(medians))
    assert _e2e("stmt_geomean_ms")(rec) == pytest.approx(
        1e3 * math.prod(medians) ** (1 / 3))
    assert _e2e("setup_s")(rec) == 12.5


def test_a_template_that_never_answered_gives_no_aggregate():
    rec = _record({"a": [1.0], "b": [2.0]})
    rec["templates"].append("c")
    assert _e2e("pass_s")(rec) is None
    assert _e2e("stmt_geomean_ms")(rec) is None


def test_rate_counts_correct_statements_over_the_real_length():
    rec = _record({"a": [0.01] * 2000})
    rec["window"][0]["correct"] = False
    assert _e2e("stmts_per_s")(rec) == pytest.approx(1999 / 50.0)


def test_p99_needs_a_thousand_samples():
    assert _e2e("stmt_p99_ms")(_record({"a": [0.01] * 999})) is None
    lat = [0.001 * i for i in range(1, 2001)]
    assert _e2e("stmt_p99_ms")(_record({"a": lat})) == pytest.approx(
        1e3 * float(np.percentile(lat, 99)))


def test_spread_is_interquartile_over_median():
    xs = [10.0, 10.2, 9.9, 10.1, 10.0, 10.4]
    q1, q2, q3 = np.percentile(xs, [25, 50, 75])
    assert stats.spread(xs) == pytest.approx((q3 - q1) / q2)
