"""PX: ``px.exchange_overflows`` (every kind) over warm-up and window: the
exchanges whose static budget overflowed.  Each made its statement re-plan
with that budget raised, which is a new shard program to compile: the part
of ``capacity_retries`` an exchange's budget caused.  ``None`` where the
program counts no exchange rows (it then has no such counter either)."""


def compute(record):
    after = record["counters_after"]
    if not any(k.startswith("px.exchange_rows") for k in after):
        return None
    return sum(v for k, v in after.items()
               if k.startswith("px.exchange_overflows"))
