"""Exact NumPy reference for TPC-H Q1: plain int64 sums per group on the
generated arrays (decimals are scaled integers), so the sums are exact
where SQLite's doubles are not.  Copied from
``oceanbase_tpu/bench/numpy_ref.py::numpy_q1``.

A reference module gives ``answer(tables, params)`` (run in the reference
child) and ``extract(names, arrays)`` (the same shape from the system's
raw result columns); the harness asks only that the two be equal.
"""

import numpy as np

_EPOCH = np.datetime64("1970-01-01", "D")
_SUMS = ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
         "count_order")


def answer(tables: dict, params: dict) -> dict:
    li = tables["lineitem"]
    cutoff = int((np.datetime64("1998-12-01", "D") - _EPOCH).astype(np.int64)) \
        - int(params["DELTA"])
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
        out[f"{key[0]}{key[1]}"] = {
            "sum_qty": int(qty[m].sum()),
            "sum_base_price": int(price[m].sum()),
            "sum_disc_price": int(disc_price[m].sum()),
            "sum_charge": int(charge[m].sum()),
            "count_order": int(m.sum()),
        }
    return out


def extract(names: list, arrays: dict) -> dict:
    n = len(arrays["l_returnflag"])
    return {f"{arrays['l_returnflag'][i]}{arrays['l_linestatus'][i]}":
            {c: int(arrays[c][i]) for c in _SUMS} for i in range(n)}
