"""device: ``memory_stats()["peak_bytes_in_use"]`` of the fullest device:
how full the chip is."""


def compute(record):
    peak = record["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
