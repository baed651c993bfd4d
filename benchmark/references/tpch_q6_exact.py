"""Exact NumPy reference for TPC-H Q6 at any substitution parameters:
``sum(l_extendedprice * l_discount)`` as a scaled integer (scale 4).
After ``oceanbase_tpu/bench/numpy_ref.py::numpy_q6``, with the bounds taken
from the parameters instead of the validation values."""

from decimal import Decimal

import numpy as np

_EPOCH = np.datetime64("1970-01-01", "D")


def _days(d) -> int:
    return int((d.astype("datetime64[D]") - _EPOCH).astype(np.int64))


def answer(tables: dict, params: dict) -> int:
    li = tables["lineitem"]
    d = np.datetime64(params["DATE"], "D")
    d0 = _days(d)
    d1 = _days(d.astype("datetime64[Y]") + np.timedelta64(1, "Y")
               + (d - d.astype("datetime64[Y]").astype("datetime64[D]")))
    disc = int(Decimal(params["DISCOUNT"]) * 100)
    qty = int(params["QUANTITY"]) * 100
    sel = ((li["l_shipdate"] >= d0) & (li["l_shipdate"] < d1)
           & (li["l_discount"] >= disc - 1) & (li["l_discount"] <= disc + 1)
           & (li["l_quantity"] < qty))
    return int((li["l_extendedprice"][sel].astype(np.int64)
                * li["l_discount"][sel].astype(np.int64)).sum())


def extract(names: list, arrays: dict) -> int:
    return int(arrays["revenue"][0])
