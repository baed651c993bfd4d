"""storage to device: ``storage.device_copy_ns`` at the window's end: seconds
spent building device relations from the store (snapshot decode, host to
device copy, bucket padding).  All of it is set-up in cells that do not
write."""


def compute(record):
    ns = record["counters_after"].get("storage.device_copy_ns")
    return None if ns is None else ns * 1e-9
