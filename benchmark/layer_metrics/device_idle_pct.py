"""device: 1 - (union of device-op intervals) / (traced window), from the
``.xplane.pb`` captures; on several chips the mean over devices."""


def compute(record):
    reduced = [c["reduced"] for c in record["captures"] if c["reduced"]]
    if not reduced:
        return None
    window = sum(r["window_s"] for r in reduced)
    return 100.0 * (1.0 - sum(r["busy_s"] for r in reduced) / window)
