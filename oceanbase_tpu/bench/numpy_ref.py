"""Exact NumPy references for TPC-H queries (Q1, Q6, Q9, Q14; Q4, Q13, Q18).

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


def _lookup(keys: np.ndarray, probe: np.ndarray):
    """-> (position in the unique ``keys`` of each ``probe`` value,
    whether it is there)."""
    order = np.argsort(keys, kind="stable")
    at = np.minimum(np.searchsorted(keys[order], probe), len(keys) - 1)
    return order[at], keys[order][at] == probe


def numpy_q9(tables: dict, color: str = "green") -> list:
    """TPC-H Q9 -> [(nation, year, sum_profit at scale 4)], ordered by
    nation, year descending; every sum an exact Python int.  The six-table
    join by ``searchsorted`` on the keys, ``amount = l_extendedprice *
    (1 - l_discount) - ps_supplycost * l_quantity`` in int64."""
    part, supp, li = tables["part"], tables["supplier"], tables["lineitem"]
    ps, orders, nation = (tables["partsupp"], tables["orders"],
                          tables["nation"])
    named = part["p_partkey"][
        np.char.find(part["p_name"].astype("U"), color) >= 0]
    sel = np.flatnonzero(np.isin(li["l_partkey"], named))
    l_part = li["l_partkey"][sel].astype(np.int64)
    l_supp = li["l_suppkey"][sel].astype(np.int64)
    width = int(max(ps["ps_suppkey"].max(), l_supp.max(initial=0))) + 1
    at_ps, in_ps = _lookup(ps["ps_partkey"].astype(np.int64) * width
                           + ps["ps_suppkey"], l_part * width + l_supp)
    at_s, in_s = _lookup(supp["s_suppkey"].astype(np.int64), l_supp)
    at_o, in_o = _lookup(orders["o_orderkey"].astype(np.int64),
                         li["l_orderkey"][sel].astype(np.int64))
    at_n, in_n = _lookup(nation["n_nationkey"].astype(np.int64),
                         supp["s_nationkey"][at_s].astype(np.int64))
    keep = in_ps & in_s & in_o & in_n
    sel = sel[keep]
    amount = (li["l_extendedprice"][sel].astype(np.int64)
              * (100 - li["l_discount"][sel].astype(np.int64))
              - ps["ps_supplycost"][at_ps[keep]].astype(np.int64)
              * li["l_quantity"][sel].astype(np.int64))
    year = orders["o_orderdate"][at_o[keep]].astype("datetime64[D]") \
        .astype("datetime64[Y]").astype(np.int64) + 1970
    sums: dict = {}
    for n, y, a in zip(nation["n_name"][at_n[keep]], year.tolist(),
                       amount.tolist()):
        sums[str(n), y] = sums.get((str(n), y), 0) + a
    return sorted(((n, y, s) for (n, y), s in sums.items()),
                  key=lambda r: (r[0], -r[1]))


def numpy_q14(tables: dict, d0: int, d1: int) -> tuple[int, int]:
    """TPC-H Q14 -> (promo revenue, total revenue), both at scale 4, of
    the lineitems shipped in ``[d0, d1)`` whose part exists."""
    li, part = tables["lineitem"], tables["part"]
    sel = (li["l_shipdate"] >= d0) & (li["l_shipdate"] < d1)
    at, there = _lookup(part["p_partkey"].astype(np.int64),
                        li["l_partkey"][sel].astype(np.int64))
    revenue = li["l_extendedprice"][sel].astype(np.int64) \
        * (100 - li["l_discount"][sel].astype(np.int64))
    promo = np.char.startswith(part["p_type"].astype("U"), "PROMO")[at]
    return int(revenue[there & promo].sum()), int(revenue[there].sum())


def numpy_q4(tables: dict, d0: int, d1: int) -> list:
    """TPC-H Q4 -> [(o_orderpriority, order_count)] by priority: the
    orders of ``[d0, d1)`` (day numbers) with at least one lineitem
    received after its commit date; ``EXISTS`` as a flag by order key."""
    orders, li = tables["orders"], tables["lineitem"]
    okey = orders["o_orderkey"].astype(np.int64)
    lkey = li["l_orderkey"].astype(np.int64)
    has_late = np.zeros(int(max(okey.max(), lkey.max())) + 1, dtype=bool)
    has_late[lkey[li["l_commitdate"] < li["l_receiptdate"]]] = True
    keep = (orders["o_orderdate"] >= d0) & (orders["o_orderdate"] < d1) \
        & has_late[okey]
    names, counts = np.unique(orders["o_orderpriority"][keep].astype("U"),
                              return_counts=True)
    return [(str(n), int(c)) for n, c in zip(names, counts)]


def numpy_q13(tables: dict, word1: str = "special",
              word2: str = "requests") -> list:
    """TPC-H Q13 -> [(c_count, custdist)] by custdist, c_count descending:
    orders whose comment does not match ``%word1%word2%`` counted a
    customer, over ALL customers (``c_count = 0`` is a group)."""
    cust, orders = tables["customer"], tables["orders"]

    def matches(s: str) -> bool:
        i = s.find(word1)
        return i >= 0 and s.find(word2, i + len(word1)) >= 0

    counted = ~np.fromiter((matches(str(s)) for s in orders["o_comment"]),
                           dtype=bool, count=len(orders["o_comment"]))
    ckey = cust["c_custkey"].astype(np.int64)
    ocust = orders["o_custkey"].astype(np.int64)
    per_key = np.bincount(ocust[counted],
                          minlength=int(max(ckey.max(), ocust.max())) + 1)
    custdist = np.bincount(per_key[ckey])
    return sorted(((int(c), int(n)) for c, n in enumerate(custdist) if n),
                  key=lambda r: (-r[1], -r[0]))


def numpy_q18(tables: dict, quantity: int = 300) -> list:
    """TPC-H Q18 WITHOUT its limit -> [(c_name, c_custkey, o_orderkey,
    o_orderdate as a day number, o_totalprice in cents, sum(l_quantity)
    at scale 2)] by o_totalprice descending, o_orderdate, then the rest
    of the row: the orders whose lineitems' quantities sum OVER
    ``quantity``."""
    cust, orders, li = (tables["customer"], tables["orders"],
                        tables["lineitem"])
    lkey = li["l_orderkey"].astype(np.int64)
    okey = orders["o_orderkey"].astype(np.int64)
    size = int(max(lkey.max(), okey.max())) + 1
    total = np.zeros(size, dtype=np.int64)
    np.add.at(total, lkey, li["l_quantity"].astype(np.int64))
    sel = np.flatnonzero(total[okey] > quantity * 100)
    at_c, in_c = _lookup(cust["c_custkey"].astype(np.int64),
                         orders["o_custkey"][sel].astype(np.int64))
    rows = [(str(cust["c_name"][c]), int(cust["c_custkey"][c]),
             int(okey[o]), int(orders["o_orderdate"][o]),
             int(orders["o_totalprice"][o]), int(total[okey[o]]))
            for o, c in zip(sel[in_c].tolist(), at_c[in_c].tolist())]
    return sorted(rows, key=lambda r: (-r[4], r[3]) + r)
