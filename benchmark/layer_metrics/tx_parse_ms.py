"""session layer: self time of ``ob:parse`` a write transaction (``begin``,
its DML statements, ``commit``: a multi-row ``INSERT`` is tens of KB of SQL
text); geometric mean over the templates that write of the median per
transaction.  ``None`` where the captures hold no write transaction."""

from benchmark.harness import write_spans


def compute(record):
    return write_spans.self_ms(record, "parse")
