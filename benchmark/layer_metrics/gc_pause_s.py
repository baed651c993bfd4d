"""background work: the change of ``runtime.gc_pause_ns`` over the window: time
the cyclic collector held the statement thread (``gc.callbacks``)."""


def compute(record):
    after = record["counters_after"].get("runtime.gc_pause_ns")
    if after is None:
        return None
    return (after - record["counters_before"].get(
        "runtime.gc_pause_ns", 0.0)) * 1e-9
