"""PX: of the joins the window's shard programs executed
(``px.joins{dist=partition_wise|broadcast|pkey|hash}``: the planner picks a
join's distribution method from where both sides lie), the share that ran
partition-wise, with no exchange.  ``None`` when the window ran no PX join,
or the program has no such counter."""

PREFIX = "px.joins{dist="
PWJ = PREFIX + "partition_wise}"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    grew = {k: v - before.get(k, 0.0) for k, v in after.items()
            if k.startswith(PREFIX)}
    total = sum(grew.values())
    if total <= 0:
        return None
    return 100.0 * grew.get(PWJ, 0.0) / total
