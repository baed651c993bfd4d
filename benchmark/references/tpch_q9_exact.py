"""Exact NumPy reference for TPC-H Q9 (Product Type Profit Measure) at any
COLOR: the six-table join carried out on the generated arrays by index
and by ``searchsorted``, ``amount = l_extendedprice * (1 - l_discount) -
ps_supplycost * l_quantity`` as an int64 at scale 4 (cents x hundredths on
both sides of the minus), summed per (nation, year) and ordered by nation,
year descending.  Imports nothing of the program.

The sums are compared bit for bit: one lineitem lost in an exchange moves
its group's sum by its amount (thousands of currency units at scale 4,
never 0 for long), and a sum kept in float32 is off in its eighth digit
where a group's sum has thirteen.

Headroom: a row's amount is under 104,949.50 x 1.00 = 1.05e9 at scale 4 in
magnitude; a (nation, year) group holds about 19,000 rows at SF10 (2e13),
and all 60M lineitems in ONE group would reach 6.3e16: int64 holds 9.2e18.
"""

import numpy as np


def _lookup(keys: np.ndarray, probe: np.ndarray):
    """-> (position in ``keys`` of each ``probe`` value, whether it is
    there); ``keys`` are unique."""
    order = np.argsort(keys, kind="stable")
    sorted_keys = keys[order]
    at = np.minimum(np.searchsorted(sorted_keys, probe), len(keys) - 1)
    return order[at], sorted_keys[at] == probe


def answer(tables: dict, params: dict) -> list:
    part, supp, li = tables["part"], tables["supplier"], tables["lineitem"]
    ps, orders, nation = (tables["partsupp"], tables["orders"],
                          tables["nation"])
    # part: p_name like '%COLOR%', as a flag by part key
    named = np.char.find(part["p_name"].astype("U"), params["COLOR"]) >= 0
    pkeys = part["p_partkey"].astype(np.int64)
    l_part = li["l_partkey"].astype(np.int64)
    l_supp = li["l_suppkey"].astype(np.int64)
    flag = np.zeros(int(max(pkeys.max(), l_part.max())) + 1, dtype=bool)
    flag[pkeys[named]] = True
    sel = np.flatnonzero(flag[l_part])
    l_part, l_supp = l_part[sel], l_supp[sel]
    # partsupp by (ps_partkey, ps_suppkey), the two keys as one integer
    width = int(max(ps["ps_suppkey"].max(), l_supp.max())) + 1
    at_ps, in_ps = _lookup(
        ps["ps_partkey"].astype(np.int64) * width
        + ps["ps_suppkey"].astype(np.int64), l_part * width + l_supp)
    at_s, in_s = _lookup(supp["s_suppkey"].astype(np.int64), l_supp)
    at_o, in_o = _lookup(orders["o_orderkey"].astype(np.int64),
                         li["l_orderkey"].astype(np.int64)[sel])
    s_nation = supp["s_nationkey"].astype(np.int64)[at_s]
    at_n, in_n = _lookup(nation["n_nationkey"].astype(np.int64), s_nation)
    keep = in_ps & in_s & in_o & in_n
    sel, at_ps, at_o, at_n = sel[keep], at_ps[keep], at_o[keep], at_n[keep]
    amount = (li["l_extendedprice"][sel].astype(np.int64)
              * (100 - li["l_discount"][sel].astype(np.int64))
              - ps["ps_supplycost"].astype(np.int64)[at_ps]
              * li["l_quantity"][sel].astype(np.int64))
    year = orders["o_orderdate"][at_o].astype("datetime64[D]") \
        .astype("datetime64[Y]").astype(np.int64) + 1970
    names = nation["n_name"].astype("U")
    # one group per (nation row, year); names are unique per nation row
    span = int(year.max() - year.min()) + 1 if len(year) else 1
    gid = at_n * span + (year - (year.min() if len(year) else 0))
    groups, inverse = np.unique(gid, return_inverse=True)
    sums = np.zeros(len(groups), dtype=np.int64)
    np.add.at(sums, inverse, amount)
    rows = [(str(names[g // span]), int(g % span + year.min()), int(s))
            for g, s in zip(groups.tolist(), sums.tolist())]
    return sorted(rows, key=lambda r: (r[0], -r[1]))


def extract(names: list, arrays: dict) -> list:
    return [(str(n), int(y), int(s)) for n, y, s in zip(
        arrays["nation"], arrays["o_year"], arrays["sum_profit"])]
