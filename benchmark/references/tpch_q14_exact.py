"""Exact NumPy reference for TPC-H Q14 at any substitution date: both sums
as int64 over the generated arrays (decimals are scaled integers: cents x
hundredths, scale 4), the join by ``p_partkey`` as an index, ``p_type`` by
its prefix, and the quotient in the arithmetic the SQL result type
prescribes (DOUBLE: ``100.0 * promo / total`` in float64).  Imports
nothing of the program.

The statement exposes the quotient alone, so that is what is compared, to
a relative 1e-12.  Why that and not bit equality: the sums are integers
below 2**53 (SF10: about 3e14), exact in a double on both sides; what is
left is the last multiply and divide, which the CPU rounds to one unit in
the last place (1.1e-16) and a TPU, which has no native float64, carries
out in emulated arithmetic a few units wide.  Why it is tight enough: ONE
lineitem row missing from the month moves either sum by about one part in
750,000 at SF10 (1.3e-6: a million times the tolerance), and sums kept in
float32 are off by 1e-7 or more.
"""

import numpy as np

_EPOCH = np.datetime64("1970-01-01", "D")
RTOL = 1e-12


class Quotient(float):
    """The quotient as ``extract`` hands it to the harness's ``!=``: equal
    to a reference within ``RTOL``."""

    def __eq__(self, other):
        if not isinstance(other, (int, float)):
            return False
        return abs(float(self) - float(other)) \
            <= RTOL * max(abs(float(self)), abs(float(other)))

    def __ne__(self, other):
        return not self.__eq__(other)

    __hash__ = float.__hash__


def _days(d) -> int:
    return int((d.astype("datetime64[D]") - _EPOCH).astype(np.int64))


def sums(tables: dict, params: dict) -> tuple[int, int]:
    """-> (promo, total): ``sum(l_extendedprice * (1 - l_discount))`` over
    the month's lineitems whose part exists, and of them the PROMO ones'."""
    li, part = tables["lineitem"], tables["part"]
    d = np.datetime64(params["DATE"], "D")
    d0 = _days(d)
    d1 = _days(d.astype("datetime64[M]") + np.timedelta64(1, "M")
               + (d - d.astype("datetime64[M]").astype("datetime64[D]")))
    sel = (li["l_shipdate"] >= d0) & (li["l_shipdate"] < d1)
    keys = li["l_partkey"][sel].astype(np.int64)
    top = int(max(part["p_partkey"].max(), keys.max() if len(keys) else 0))
    exists = np.zeros(top + 1, dtype=bool)
    promo = np.zeros(top + 1, dtype=bool)
    exists[part["p_partkey"]] = True
    promo[part["p_partkey"]] = np.char.startswith(
        part["p_type"].astype("U"), "PROMO")
    revenue = li["l_extendedprice"][sel].astype(np.int64) \
        * (100 - li["l_discount"][sel].astype(np.int64))
    return (int(revenue[promo[keys]].sum()),
            int(revenue[exists[keys]].sum()))


def answer(tables: dict, params: dict):
    promo, total = sums(tables, params)
    return None if total == 0 else 100.0 * promo / total


def extract(names: list, arrays: dict):
    value = arrays["promo_revenue"][0]
    return None if value is None or np.isnan(value) \
        else Quotient(float(value))
