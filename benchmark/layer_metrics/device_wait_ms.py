"""operators: geometric mean over templates of the median
``gv$sql_audit.device_s``.  ``device_s`` is the time the statement waited
for the device at the result boundary, not device busy time."""

from benchmark.harness import stats


def compute(record):
    samples = [{"template": s["template"], "device_s": s["audit"]["device_s"]}
               for s in record["window"] if s.get("audit")]
    medians = stats.template_medians(samples, value="device_s")
    if len(medians) < len(record["templates"]) or min(medians.values()) <= 0:
        return None
    return 1e3 * stats.geomean(medians.values())
