"""storage to device: median over the traced statements of the summed durations
of ``ob:granule.fetch`` inside a statement: the host's decode of the
statement's granules from the segments' chunks (the producer thread: only
the columns the plan reaches, strings as dictionary codes, zone maps and
MVCC applied).  ``None`` where no traced statement streamed."""

from benchmark.harness import granule_spans


def compute(record):
    return granule_spans.per_statement_ms(record, "fetch")
