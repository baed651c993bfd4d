"""PX: median self time of ``ob:px.shard`` over the traced statements: what a
statement spends putting its tables on the mesh.  A table with no declared
partitioning goes device -> host -> devices in here, per statement; a
hash-partitioned one is handed over where it lies (its partitions' copies
are built once, under the child span ``ob:px.partition_build``, which is
not in here)."""

from benchmark.harness import program_spans


def compute(record):
    return program_spans.self_ms(record, "px.shard")
