"""transactions: self time of a write transaction's DML spans: ``ob:dml.bind``
(text to typed rows, a WHERE clause to a predicate), ``dml.match`` with its
leaves ``dml.candidates`` (the chunk decode on the host), ``dml.predicate``,
``dml.assign`` and ``dml.rows``, and ``dml.write`` (the loop into
``TransService.write``); geometric mean over the templates that write of
the median per transaction.  ``None`` where the captures hold no write
transaction."""

from benchmark.harness import write_spans

SPANS = ("dml.bind", "dml.match", "dml.candidates", "dml.predicate",
         "dml.assign", "dml.rows", "dml.write")


def compute(record):
    return write_spans.self_ms(record, *SPANS)
