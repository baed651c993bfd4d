"""replicated log: replicas that had a group append on disk when it was
acknowledged, the leader's own included: the change of ``palf.acks`` over
the change of ``palf.appends`` in the window.  3 of 3 is what the
configuration's three in-process replicas give; a quorum is 2.  ``None``
where the program has no such counter or the window appended nothing."""

from benchmark.harness import write_spans


def compute(record):
    return write_spans.counter_ratio(record, "palf.acks", "palf.appends")
