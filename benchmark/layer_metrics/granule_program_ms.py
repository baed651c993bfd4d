"""operators: median over the traced statements of the summed durations of
``ob:granule.program`` inside a statement: dispatch and wait of the chunk
program, a granule each: scan, filter, project, probe and the partial
aggregate.  ``None`` where no traced statement streamed."""

from benchmark.harness import granule_spans


def compute(record):
    return granule_spans.per_statement_ms(record, "program")
