"""device: the share of the captures' device-idle time that lies under no
``ob:`` leaf span of the program: idle gaps that cannot be put down to a
phase (``harness/program_spans.py``)."""

from benchmark.harness import program_spans


def compute(record):
    return program_spans.idle_under_no_span_pct(record)
