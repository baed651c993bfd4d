"""PX: of the joins the window's shard programs executed
(``px.joins{dist=partition_wise|broadcast|pkey|hash}``), the share whose
rows crossed chips in a repartition: PKEY (one side moves to the other's
partitions) or HASH-HASH (both move).  ``None`` when the window ran no PX
join, or the program has no such counter."""

PREFIX = "px.joins{dist="
MOVED = (PREFIX + "pkey}", PREFIX + "hash}")


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    grew = {k: v - before.get(k, 0.0) for k, v in after.items()
            if k.startswith(PREFIX)}
    total = sum(grew.values())
    if total <= 0:
        return None
    return 100.0 * sum(grew.get(k, 0.0) for k in MOVED) / total
