"""Write transactions in the traced captures.

A write transaction is a traced ``bench:execute`` span (``begin`` to
``commit``'s return, one per transaction of a set) in which the program
committed: its reduction (``program_spans.reduce_profile``) holds an
``ob:tx.commit`` event.  No template is known by name here.

A span metric of the write path is the geometric mean, over the templates
that have write transactions, of the median per transaction: every
template counts alike, as in the end-to-end latency it should move.
Where the program opens no such span (a parent commit of the PR that
brought them) there is no write transaction and every reader returns
``None``.
"""

from __future__ import annotations

from . import program_spans, stats

COMMIT = "tx.commit"


def transactions(record) -> dict | None:
    """template -> its write transactions' reductions, for the templates
    that have one; ``None`` where the captures hold none."""
    reds = program_spans.load(record)
    if reds is None:
        return None
    out = {t: [st for st in red["statements"] if COMMIT in st["self_ns"]]
           for t, red in reds.items()}
    out = {t: sts for t, sts in out.items() if sts}
    return out or None


def _geomean_of_medians(per_template: dict) -> float | None:
    medians = [stats.median(xs) for xs in per_template.values()]
    return stats.geomean(medians) if min(medians) > 0 else None


def self_ms(record, *names: str) -> float | None:
    """The summed self time of the named spans a write transaction, in ms."""
    txs = transactions(record)
    if txs is None:
        return None
    return _geomean_of_medians({
        t: [sum(st["self_ns"].get(n, 0.0) for n in names) * 1e-6
            for st in sts] for t, sts in txs.items()})


def unowned_ms(record) -> float | None:
    """What no ``ob:`` leaf span covers of a write transaction, in ms."""
    txs = transactions(record)
    if txs is None:
        return None
    return _geomean_of_medians({
        t: [st["unowned_ns"] * 1e-6 for st in sts] for t, sts in txs.items()})


def counter_ratio(record, over: str, under: str,
                  but: tuple = ()) -> float | None:
    """The window's change of the counter ``over`` divided by that of
    ``under``, each summed over its label sets (``but`` the series named
    there); ``None`` where the program has no series of either name, or
    the window moved none of ``under``."""
    before, after = record["counters_before"], record["counters_after"]

    def change(name):
        keys = [k for k in after
                if k.split("{")[0] == name and k not in but]
        if not keys:
            return None
        return sum(after[k] - before.get(k, 0.0) for k in keys)

    top, bottom = change(over), change(under)
    if top is None or bottom is None or bottom <= 0:
        return None
    return top / bottom
