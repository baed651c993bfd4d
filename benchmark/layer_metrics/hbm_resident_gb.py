"""device: bytes of the tables' device copies the relation cache holds at
the window's end (``storage.device_copy_bytes``: added when a copy is
cached, taken off when it goes), in GB: what a deployment keeps resident,
beside ``hbm_peak_gb``'s high-water mark of everything.  ``None`` where the
program has no such counter."""


def compute(record):
    nbytes = record["counters_after"].get("storage.device_copy_bytes")
    return None if nbytes is None else nbytes * 1e-9
