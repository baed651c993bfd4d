"""storage -> device: the benchmark's own span around generate + direct
load + ANALYZE of the cell's tables."""


def compute(record):
    return record["phases"].get("load")
