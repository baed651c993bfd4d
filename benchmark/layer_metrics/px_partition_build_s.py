"""PX: ``px.partition_build_ns`` at the window's start: seconds spent
cutting the partitions of hash-partitioned tables out of their relations
and copying each to its own chip (span ``px.partition_build``).  All of it
is set-up in a cell that does not write.  ``None`` where the program has no
such counter."""


def compute(record):
    ns = record["counters_before"].get("px.partition_build_ns")
    return None if ns is None else ns * 1e-9
