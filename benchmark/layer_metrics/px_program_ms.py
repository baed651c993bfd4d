"""PX: median self time of ``ob:px.program`` over the traced statements:
the shard program's cache key, its lookup and its enqueue (a first
execution's trace and compile are JAX's own events, booked apart)."""

from benchmark.harness import program_spans


def compute(record):
    return program_spans.self_ms(record, "px.program")
