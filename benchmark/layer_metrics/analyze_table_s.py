"""storage to device: seconds ``ANALYZE TABLE`` spent gathering the cell's
statistics (``storage.analyze_ns``, the span ``analyze``), at the window's
start: set-up.  ``None`` where the program has no such counter."""


def compute(record):
    ns = record["counters_before"].get("storage.analyze_ns")
    return None if ns is None else ns * 1e-9
