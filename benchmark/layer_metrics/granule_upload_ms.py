"""storage to device: median over the traced statements of the summed durations
of ``ob:granule.upload`` inside a statement: the copy of the statement's
granules host -> device, each waited for (the producer thread; it overlaps
the previous granule's program).  ``None`` where no traced statement
streamed."""

from benchmark.harness import granule_spans


def compute(record):
    return granule_spans.per_statement_ms(record, "upload")
