"""replicated log: bytes handed to the log files' ``write()``, every replica,
a base-table row written: the change of ``palf.append_bytes`` over the
change of ``tx.rows_written`` (``op=insert|update|delete``; index entries,
``op=index``, are not user rows) in the window.  ``None`` where the program
has no such counter or the window wrote no row."""

from benchmark.harness import write_spans

INDEX = "tx.rows_written{op=index}"


def compute(record):
    return write_spans.counter_ratio(record, "palf.append_bytes",
                                     "tx.rows_written", but=(INDEX,))
