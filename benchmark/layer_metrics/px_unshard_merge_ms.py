"""PX: median self time of ``ob:px.unshard`` + ``ob:px.merge`` over the
traced statements: gathering the shards' results to one device and the
coordinator's part of the plan (final merge of partial aggregates, the
top chain) as enqueued by the host."""

from benchmark.harness import program_spans


def compute(record):
    return program_spans.self_ms(record, "px.unshard", "px.merge")
