"""storage to device: median self time of ``ob:tables`` (the statement's
device relations: ``_table_snapshot``, ANN and index prefilters, index-probe
sidecars; a device copy built on a cache miss is its child
``ob:storage.device_copy`` and not in here) over the traced statements."""

from benchmark.harness import program_spans


def compute(record):
    return program_spans.self_ms(record, "tables")
