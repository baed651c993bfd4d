"""session layer: median self time of ``ob:materialize`` (the result relation
to host columns, ``Session._materialize``) over the traced statements."""

from benchmark.harness import program_spans


def compute(record):
    return program_spans.self_ms(record, "materialize")
