"""Exact NumPy reference for TPC-H Q18 (Large Volume Customer) at any
QUANTITY: ``l_quantity`` summed by order key (``bincount``), the orders
whose sum is OVER the threshold, each joined to its customer by index,
its lineitems summed again as the outer query joins them, ordered by
``o_totalprice`` descending then ``o_orderdate``, the first 100 rows.
Imports nothing of the program.

Every value is a string, an integer, a date (days since 1970) or a
DECIMAL as its scaled integer (cents; ``l_quantity`` at scale 2), so the
comparison is bit-equal and ordered.  ``bincount`` sums in float64: a
partial sum here is an integer under 7 x 50 x 100, far inside the 2^53 a
double holds exactly.

The one latitude: rows that tie on BOTH sort keys.  Within the 100 they
may come in either order, and where such a tie lies across the 100th
place the statement may return any of the tied rows.  ``answer`` gives
the 100 rows in a canonical order (ties by the rest of the row) and ALL
rows of the full result that tie with the 100th; ``extract`` wraps the
program's rows in ``Top`` whose comparison checks: as many rows, in the
order of the sort keys, every row off the cut equal to the reference's,
and every row on the cut one of the tied rows, none twice.

What the comparison catches (``benchmark/tests/drive_q18_faults.py``): a
lineitem lost inside a qualifying order lowers that row's sum and may
drop the order under the threshold (a row missing, another in its
place); ``>=`` for ``>`` admits the orders at exactly the threshold.
"""

import numpy as np

LIMIT = 100


def _lookup(keys: np.ndarray, probe: np.ndarray):
    """-> (position in ``keys`` of each ``probe`` value, whether it is
    there); ``keys`` are unique."""
    order = np.argsort(keys, kind="stable")
    sorted_keys = keys[order]
    at = np.minimum(np.searchsorted(sorted_keys, probe), len(keys) - 1)
    return order[at], sorted_keys[at] == probe


def _sort_key(row):
    return (-row[4], row[3])        # o_totalprice desc, o_orderdate


def full_result(tables: dict, quantity: int) -> list:
    """Every row of the statement without its LIMIT, in canonical order."""
    cust, orders, li = tables["customer"], tables["orders"], \
        tables["lineitem"]
    lkey = li["l_orderkey"].astype(np.int64)
    lqty = li["l_quantity"].astype(np.int64)
    okey = orders["o_orderkey"].astype(np.int64)
    size = int(max(lkey.max(), okey.max())) + 1
    by_order = np.bincount(lkey, weights=lqty.astype(np.float64),
                           minlength=size).astype(np.int64)
    large = by_order > quantity * 100       # l_quantity is at scale 2
    sel = np.flatnonzero(large[okey])
    at_c, in_c = _lookup(cust["c_custkey"].astype(np.int64),
                         orders["o_custkey"].astype(np.int64)[sel])
    sel, at_c = sel[in_c], at_c[in_c]
    # the outer query's own join with lineitem and its sum
    mine = np.zeros(size, dtype=bool)
    mine[okey[sel]] = True
    lines = np.flatnonzero(mine[lkey])
    total = np.bincount(lkey[lines], weights=lqty[lines].astype(np.float64),
                        minlength=size).astype(np.int64)
    has_lines = np.bincount(lkey[lines], minlength=size) > 0
    rows = [(str(cust["c_name"][c]), int(cust["c_custkey"][c]),
             int(okey[o]), int(orders["o_orderdate"][o]),
             int(orders["o_totalprice"][o]), int(total[okey[o]]))
            for o, c in zip(sel.tolist(), at_c.tolist())
            if has_lines[okey[o]]]
    return sorted(rows, key=lambda r: _sort_key(r) + r)


def answer(tables: dict, params: dict) -> dict:
    rows = full_result(tables, int(params["QUANTITY"]))
    top = rows[:LIMIT]
    cut = _sort_key(top[-1]) if len(rows) > LIMIT else None
    return {"rows": top,
            "tied_at_cut": [r for r in rows if _sort_key(r) == cut]}


class Top(list):
    """The program's rows; equal to an ``answer`` within the latitude the
    module's docstring states."""

    def __eq__(self, want):
        rows, tied = want["rows"], want["tied_at_cut"]
        if len(self) != len(rows):
            return False
        keys = [_sort_key(r) for r in self]
        if keys != sorted(keys):
            return False
        cut = _sort_key(tied[0]) if tied else None
        mine = sorted(self, key=lambda r: _sort_key(r) + tuple(r))
        off_cut = [r for r in mine if _sort_key(r) != cut]
        on_cut = [r for r in mine if _sort_key(r) == cut]
        return off_cut == [r for r in rows if _sort_key(r) != cut] \
            and len(set(on_cut)) == len(on_cut) \
            and set(on_cut) <= set(map(tuple, tied))

    def __ne__(self, want):
        return not self.__eq__(want)

    __hash__ = None


def extract(names: list, arrays: dict) -> Top:
    (sum_name,) = [n for n in names if n not in (
        "c_name", "c_custkey", "o_orderkey", "o_orderdate", "o_totalprice")]
    days = np.asarray(arrays["o_orderdate"]).astype("datetime64[D]") \
        .astype(np.int64)
    return Top((str(n), int(c), int(o), int(d), int(p), int(q))
               for n, c, o, d, p, q in zip(
                   arrays["c_name"], arrays["c_custkey"],
                   arrays["o_orderkey"], days, arrays["o_totalprice"],
                   arrays[sum_name]))
