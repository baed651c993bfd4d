"""storage to device: the rate of the granules' copies host -> device: the
window's ``granule.upload_bytes`` over the seconds its ``ob:granule.upload``
spans took, the latter as each template's window executions times the
upload seconds of its traced execution (a span waits for its copy, so the
bytes over it are the copy's own rate, not the pipeline's).  ``None`` where
nothing was uploaded or no capture holds the spans."""

from benchmark.harness import granule_spans

SERIES = "granule.upload_bytes"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    nbytes = after.get(SERIES, 0.0) - before.get(SERIES, 0.0)
    got = granule_spans.load(record)
    if nbytes <= 0 or got is None:
        return None
    sent = granule_spans.window_executions(record)
    seconds = sum(
        sent.get(t, 0) * sum(st["seconds"]["upload"] for st in sts) / len(sts)
        for t, sts in got.items() if sts)
    return nbytes * 1e-9 / seconds if seconds > 0 else None
