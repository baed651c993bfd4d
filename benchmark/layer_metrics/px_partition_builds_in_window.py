"""PX: change of ``px.partition_builds`` over the window: partition copies
placed on their chips while statements were timed.  Expected 0: a table
that does not change keeps its copies.  ``None`` where the program has no
such counter."""

NAME = "px.partition_builds"


def compute(record):
    after = record["counters_after"].get(NAME)
    if after is None:
        return None
    return after - record["counters_before"].get(NAME, 0.0)
