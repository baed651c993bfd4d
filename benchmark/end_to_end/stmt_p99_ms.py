"""99th percentile of client latency over all statements of the window;
only where the window holds some thousands (ten samples beyond it need a
thousand)."""

from benchmark.harness import stats


def compute(record):
    lat = [s["latency_s"] for s in record["window"] if s["error"] is None]
    if len(lat) < 1000:
        return None
    return 1e3 * stats.percentile(lat, 99.0)
