"""PX: how much of the static PKEY and HASH-HASH exchange buffers is rows:
``px.exchange_rows{kind=pkey|hash}`` (live rows received, summed over the
mesh) over ``px.exchange_lanes{kind=pkey|hash}`` (the buffers' lanes a
shard, times the shards) in the window.  ``None`` when the window moved
nothing that way, or the program counts no exchange rows."""

KINDS = ("pkey", "hash")


def _grew(record, series: str) -> float:
    before, after = record["counters_before"], record["counters_after"]
    return sum(after.get(k, 0.0) - before.get(k, 0.0)
               for k in (f"{series}{{kind={kind}}}" for kind in KINDS))


def compute(record):
    if not any(k.startswith("px.exchange_rows")
               for k in record["counters_after"]):
        return None
    lanes = _grew(record, "px.exchange_lanes") * record["device"]["count"]
    if lanes <= 0:
        return None
    return 100.0 * _grew(record, "px.exchange_rows") / lanes
