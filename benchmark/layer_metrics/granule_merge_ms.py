"""operators: median over the traced statements of the summed durations of
``ob:granule.merge`` inside a statement: the partial states to the result:
the cached program that unions them, finishes the aggregate and applies the
coordinator chain, and the wait for it.  ``None`` where no traced statement
streamed."""

from benchmark.harness import granule_spans


def compute(record):
    return granule_spans.per_statement_ms(record, "merge")
