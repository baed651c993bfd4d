"""replicated log: durable log flushes a committed transaction: the change of
``palf.fsyncs`` over the change of ``tx.commits`` (every path but ``empty``:
a transaction with no participant logs nothing) in the window.  Three
in-process replicas flush once each a commit: 3, and more where an append
first had to run an election (two catch-up flushes of nothing and a no-op
entry's three).  It falls when a flush is skipped, shared or deferred.
``None`` where the program has no such counter or the window committed
nothing."""

from benchmark.harness import write_spans

EMPTY = "tx.commits{path=empty}"


def compute(record):
    return write_spans.counter_ratio(record, "palf.fsyncs", "tx.commits",
                                     but=(EMPTY,))
