"""replicated log: what a commit waits for its log: self time of
``ob:palf.append`` (leader append, shipping, the commit rule), every
``palf.persist`` under it (encode, write, flush, ``os.fsync``, one a replica
that wrote) and ``palf.apply`` a write transaction; geometric mean over the
templates that write of the median per transaction.  ``None`` where the
captures hold no write transaction."""

from benchmark.harness import write_spans

SPANS = ("palf.append", "palf.persist", "palf.apply")


def compute(record):
    return write_spans.self_ms(record, *SPANS)
