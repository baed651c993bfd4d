"""storage to device: median, over the traced statements that applied a
committed delta to a resident relation, of the self time of
``ob:storage.delta_apply`` plus its child ``ob:storage.delta_read`` (the
host's read of the commit log and the memtables): what the first read
after commits pays instead of a rebuild.  ``None`` where no traced
statement has such a span (a program without the mechanism)."""

from benchmark.harness import program_spans, stats

SPANS = ("storage.delta_apply", "storage.delta_read")


def compute(record):
    reds = program_spans.load(record)
    if reds is None:
        return None
    xs = [sum(st["self_ns"].get(n, 0.0) for n in SPANS) * 1e-6
          for red in reds.values() for st in red["statements"]]
    xs = [x for x in xs if x > 0]
    return stats.median(xs) if xs else None
