"""Exact NumPy reference for TPC-H Q4 (Order Priority Checking) at any
DATE: the orders of the quarter that have at least one lineitem received
after its commit date, counted by priority.  ``EXISTS`` is a boolean flag
indexed by order key (set once however many late lines an order has), the
quarter's bounds are days since 1970, the answer is strings and integer
counts ordered by priority.  Imports nothing of the program.

Compared bit for bit and in order.  What the comparison catches (shown in
``benchmark/tests/drive_q18_faults.py``): a semi-join that emits an order
once a matching LINE counts an order of three late lines three times, so
every priority's count rises by a factor of about 2.4.
"""

import numpy as np


def _quarter(date: str) -> tuple[int, int]:
    """``[date, date + 3 months)`` as days since 1970-01-01."""
    first = np.datetime64(date, "D")
    month = first.astype("datetime64[M]")
    day = (first - month.astype("datetime64[D]")).astype(np.int64)
    end = (month + 3).astype("datetime64[D]") + day
    return int(first.astype(np.int64)), int(end.astype(np.int64))


def answer(tables: dict, params: dict) -> list:
    orders, li = tables["orders"], tables["lineitem"]
    lo, hi = _quarter(params["DATE"])
    okey = orders["o_orderkey"].astype(np.int64)
    lkey = li["l_orderkey"].astype(np.int64)
    late = li["l_commitdate"].astype(np.int64) \
        < li["l_receiptdate"].astype(np.int64)
    has_late = np.zeros(int(max(okey.max(), lkey.max())) + 1, dtype=bool)
    has_late[lkey[late]] = True
    odate = orders["o_orderdate"].astype(np.int64)
    keep = (odate >= lo) & (odate < hi) & has_late[okey]
    names, counts = np.unique(orders["o_orderpriority"][keep].astype("U"),
                              return_counts=True)
    return [(str(n), int(c)) for n, c in zip(names, counts)]


def extract(names: list, arrays: dict) -> list:
    return [(str(p), int(n)) for p, n in zip(arrays["o_orderpriority"],
                                             arrays["order_count"])]
