"""storage to device: what a direct load costs a row: the program's own
``storage.bulk_load_ns`` over all its phases (encode, sort, segment,
persist, device_copy) over ``storage.bulk_load_rows``, at the window's
start (all of it is set-up).  ``None`` where the program has no such
counter."""

PREFIX = "storage.bulk_load_ns{"


def compute(record):
    counters = record["counters_before"]
    rows = counters.get("storage.bulk_load_rows")
    if not rows:
        return None
    ns = sum(v for k, v in counters.items() if k.startswith(PREFIX))
    return ns / rows * 1e-3
