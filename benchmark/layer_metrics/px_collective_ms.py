"""PX: device time of the collectives (all-to-all, all-gather, all-reduce,
collective-permute, reduce-scatter) in the traced pass, on the device that
spent most in them."""


def compute(record):
    worst = {}
    for cap in record["captures"]:
        if not cap["reduced"]:
            return None
        for d in cap["reduced"]["devices"]:
            worst[d["name"]] = worst.get(d["name"], 0.0) \
                + d["collective_s"] / cap["executions"]
    return 1e3 * max(worst.values()) if worst else None
