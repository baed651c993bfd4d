"""session layer: median over the traced statements of ``bench:execute`` minus
the union of the program's ``ob:`` LEAF spans: host time of a statement that
no phase of the program owns (``harness/program_spans.py``)."""

from benchmark.harness import program_spans


def compute(record):
    return program_spans.unowned_ms(record)
