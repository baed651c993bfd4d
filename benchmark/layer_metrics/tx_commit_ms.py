"""transactions: self time of ``ob:tx.commit`` (GTS, the state machine, lock
release) with ``tx.log_encode`` (the redo records' way to bytes) and
``tx.apply`` (versions made visible, the commit log a later read's delta
comes from) a write transaction; geometric mean over the templates that
write of the median per transaction.  ``None`` where the captures hold no
write transaction."""

from benchmark.harness import write_spans

SPANS = ("tx.commit", "tx.log_encode", "tx.apply")


def compute(record):
    return write_spans.self_ms(record, *SPANS)
