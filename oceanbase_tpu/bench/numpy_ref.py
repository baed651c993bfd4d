"""Exact NumPy references for the two scan-aggregate TPC-H queries.

Independent of the engine: plain int64 arithmetic on the generated
arrays (decimals are scaled integers, dates are day numbers), so the
sums are exact where the SQLite oracle's doubles are not.
"""

from __future__ import annotations

import numpy as np


def numpy_q1(li: dict, cutoff: int) -> dict:
    """TPC-H Q1 -> {(returnflag, linestatus): {sum_qty, sum_base_price,
    sum_disc_price (scale 4), sum_charge (scale 6), count_order}}, every
    value an exact Python int."""
    sel = li["l_shipdate"] <= cutoff
    rf = li["l_returnflag"][sel].astype("U1")
    ls = li["l_linestatus"][sel].astype("U1")
    qty = li["l_quantity"][sel].astype(np.int64)
    price = li["l_extendedprice"][sel].astype(np.int64)
    disc = li["l_discount"][sel].astype(np.int64)
    tax = li["l_tax"][sel].astype(np.int64)
    disc_price = price * (100 - disc)
    charge = disc_price * (100 + tax)
    ukeys, codes = np.unique(np.char.add(rf, ls), return_inverse=True)
    out = {}
    for g, key in enumerate(ukeys):
        m = codes == g
        out[(str(key[0]), str(key[1]))] = {
            "sum_qty": int(qty[m].sum()),
            "sum_base_price": int(price[m].sum()),
            "sum_disc_price": int(disc_price[m].sum()),
            "sum_charge": int(charge[m].sum()),
            "count_order": int(m.sum()),
        }
    return out


def numpy_q6(li: dict, d0: int, d1: int) -> int:
    """TPC-H Q6 -> sum(extendedprice * discount), scale 4, exact."""
    sel = (
        (li["l_shipdate"] >= d0) & (li["l_shipdate"] < d1)
        & (li["l_discount"] >= 5) & (li["l_discount"] <= 7)
        & (li["l_quantity"] < 2400)
    )
    return int((li["l_extendedprice"][sel].astype(np.int64)
                * li["l_discount"][sel].astype(np.int64)).sum())
