"""session layer: median, over the window's statements, of the client's
latency minus the time the statement waited for the device
(``gv$sql_audit.device_s``): everything the host does for a statement,
audited phase or not, result fetch included."""

from benchmark.harness import stats


def compute(record):
    rows = [s for s in record["window"] if s.get("audit")]
    if not any(s["audit"]["device_s"] > 0 for s in rows):
        return None  # this path does not record the split (PX today)
    return 1e3 * stats.median(
        [s["latency_s"] - s["audit"]["device_s"] for s in rows])
