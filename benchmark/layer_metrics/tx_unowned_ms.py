"""session layer: what no ``ob:`` leaf span covers of a write transaction
(``harness/program_spans.py``'s ``unowned_ns``: the time between a parent
span's children is glue and has no name); geometric mean over the templates
that write of the median per transaction.  It says how much of a write
transaction the other readers explain.  ``None`` where the captures hold no
write transaction."""

from benchmark.harness import write_spans


def compute(record):
    return write_spans.unowned_ms(record)
