"""TPC-H data from a seed, array for array ``tpch.py``'s, in a third of the
memory: the generator for a scale factor at which two copies of the data
(the run's and the reference child's) have to fit one host.

``tpch.generate`` makes a Python string per ROW for the seven
low-cardinality string columns of ``orders`` and ``lineitem``
(``.astype(object)`` of a ``<U`` array, a comprehension): at SF10 that is
15 GB of the 22.7 GB a process holds at its peak (measured: ``VmHWM``
22,671,848 kB for ``generate(10.0, seed)``), and the run and its
reference child generate at the same time, 44.0 GB together on a host of
45.  Here those columns index a pool of their few distinct strings, so a
row costs a pointer.  Every draw is the same draw in the same order
(``rng.choice(a, n)`` of a pool IS ``rng.choice(len(a), n)`` and a take),
so the tables are equal to ``tpch.generate``'s for every scale and seed
(``benchmark/tests/test_sf10_cell.py`` holds them to it).  The vocabulary,
the helper functions and the refresh functions are ``tpch.py``'s own,
loaded from the file beside this one; nothing of the program is imported.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "_bench_datasets_tpch_for_pooled",
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch.py"))
_tpch = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_tpch)

for _name in ("REGIONS", "NATIONS", "COLORS", "TYPE_S1", "TYPE_S2", "TYPE_S3",
              "CONTAINER_S1", "CONTAINER_S2", "SEGMENTS", "PRIORITIES",
              "SHIPMODES", "SHIPINSTRUCT", "_comment_pool", "_money",
              "_retail_price", "_START", "_END", "_CURRENT", "date_to_days",
              "PRIMARY_KEYS", "refresh", "refresh_new_sales",
              "refresh_old_sales"):
    globals()[_name] = getattr(_tpch, _name)


def _pool(strings) -> np.ndarray:
    return np.array(list(strings), dtype=object)


def generate(scale: float, seed: int):
    """All 8 tables -> (tables, types), array for array what
    ``tpch.generate`` gives for the same arguments (the same draws in the
    same order); the low-cardinality string columns of ``orders`` and
    ``lineitem`` share the strings of a pool."""
    sf = scale
    rng = np.random.default_rng(seed)
    n_part = int(200_000 * sf)
    n_supp = max(int(10_000 * sf), 10)
    n_cust = int(150_000 * sf)
    n_ord = int(1_500_000 * sf)

    types: dict[str, tuple] = {}
    tables: dict[str, dict[str, np.ndarray]] = {}

    # ---- region / nation ------------------------------------------------
    tables["region"] = {
        "r_regionkey": np.arange(5, dtype=np.int64),
        "r_name": np.array(REGIONS, dtype=object),
        "r_comment": _comment_pool(rng, 5),
    }
    nname = np.array([n for n, _ in NATIONS], dtype=object)
    nreg = np.array([r for _, r in NATIONS], dtype=np.int64)
    tables["nation"] = {
        "n_nationkey": np.arange(25, dtype=np.int64),
        "n_name": nname,
        "n_regionkey": nreg,
        "n_comment": _comment_pool(rng, 25),
    }

    # ---- supplier -------------------------------------------------------
    s_comment_pool = _comment_pool(
        rng, max(200, n_supp // 10), trigger=("Customer", "Complaints"),
        trigger_frac=0.005,
    )
    tables["supplier"] = {
        "s_suppkey": np.arange(1, n_supp + 1, dtype=np.int64),
        "s_name": np.array([f"Supplier#{i:09d}" for i in range(1, n_supp + 1)],
                           dtype=object),
        "s_address": _comment_pool(rng, max(100, n_supp // 20))[
            rng.integers(0, max(100, n_supp // 20), n_supp)],
        "s_nationkey": rng.integers(0, 25, n_supp, dtype=np.int64),
        "s_phone": np.array(
            [f"{rng.integers(10, 35)}-{rng.integers(100, 999)}-{rng.integers(100, 999)}-{rng.integers(1000, 9999)}"
             for _ in range(n_supp)], dtype=object),
        "s_acctbal": _money(rng, -99999, 999999, n_supp),
        "s_comment": s_comment_pool[rng.integers(0, len(s_comment_pool), n_supp)],
    }
    types["s_acctbal"] = ("decimal", 15, 2)

    # ---- part -----------------------------------------------------------
    pname_words = rng.choice(np.array(COLORS), (n_part, 5))
    p_name = np.array([" ".join(row) for row in pname_words], dtype=object)
    p_mfgr_i = rng.integers(1, 6, n_part)
    p_brand_i = p_mfgr_i * 10 + rng.integers(1, 6, n_part)
    p_type = (
        np.char.add(
            np.char.add(
                rng.choice(np.array(TYPE_S1), n_part).astype("U16"), " "
            ),
            np.char.add(
                np.char.add(rng.choice(np.array(TYPE_S2), n_part).astype("U16"), " "),
                rng.choice(np.array(TYPE_S3), n_part).astype("U16"),
            ),
        )
    ).astype(object)
    p_container = np.char.add(
        np.char.add(rng.choice(np.array(CONTAINER_S1), n_part).astype("U8"), " "),
        rng.choice(np.array(CONTAINER_S2), n_part).astype("U8"),
    ).astype(object)
    p_retail = _retail_price(np.arange(1, n_part + 1))
    tables["part"] = {
        "p_partkey": np.arange(1, n_part + 1, dtype=np.int64),
        "p_name": p_name,
        "p_mfgr": np.array([f"Manufacturer#{i}" for i in p_mfgr_i], dtype=object),
        "p_brand": np.array([f"Brand#{i}" for i in p_brand_i], dtype=object),
        "p_type": p_type,
        "p_size": rng.integers(1, 51, n_part, dtype=np.int64),
        "p_container": p_container,
        "p_retailprice": p_retail,
        "p_comment": _comment_pool(rng, max(100, n_part // 50))[
            rng.integers(0, max(100, n_part // 50), n_part)],
    }
    types["p_retailprice"] = ("decimal", 15, 2)

    # ---- partsupp (4 suppliers per part) --------------------------------
    n_ps = n_part * 4
    ps_partkey = np.repeat(np.arange(1, n_part + 1, dtype=np.int64), 4)
    ps_suppkey = (
        (ps_partkey + (np.tile(np.arange(4), n_part))
         * ((n_supp // 4) + 1)) % n_supp + 1
    ).astype(np.int64)
    tables["partsupp"] = {
        "ps_partkey": ps_partkey,
        "ps_suppkey": ps_suppkey,
        "ps_availqty": rng.integers(1, 10000, n_ps, dtype=np.int64),
        "ps_supplycost": _money(rng, 100, 100001, n_ps),
        "ps_comment": _comment_pool(rng, 200)[rng.integers(0, 200, n_ps)],
    }
    types["ps_supplycost"] = ("decimal", 15, 2)

    # ---- customer -------------------------------------------------------
    tables["customer"] = {
        "c_custkey": np.arange(1, n_cust + 1, dtype=np.int64),
        "c_name": np.array([f"Customer#{i:09d}" for i in range(1, n_cust + 1)],
                           dtype=object),
        "c_address": _comment_pool(rng, max(100, n_cust // 30))[
            rng.integers(0, max(100, n_cust // 30), n_cust)],
        "c_nationkey": rng.integers(0, 25, n_cust, dtype=np.int64),
        "c_phone": np.array(
            [f"{10 + (i % 25)}-{100 + (i * 7) % 900}-{100 + (i * 13) % 900}-{1000 + (i * 31) % 9000}"
             for i in range(1, n_cust + 1)], dtype=object),
        "c_acctbal": _money(rng, -99999, 999999, n_cust),
        "c_mktsegment": rng.choice(np.array(SEGMENTS), n_cust).astype(object),
        "c_comment": _comment_pool(rng, max(200, n_cust // 30))[
            rng.integers(0, max(200, n_cust // 30), n_cust)],
    }
    types["c_acctbal"] = ("decimal", 15, 2)

    # ---- orders ---------------------------------------------------------
    # spec: only 2/3 of customers have orders (clustered on odd custkeys)
    o_orderkey = np.arange(1, n_ord + 1, dtype=np.int64)
    o_custkey = rng.integers(1, max(n_cust, 2), n_ord, dtype=np.int64)
    o_custkey = np.where(o_custkey % 3 == 0, np.maximum(o_custkey - 1, 1), o_custkey)
    o_orderdate = rng.integers(_START, _END - 151, n_ord, dtype=np.int64)
    o_comment_pool = _comment_pool(
        rng, max(500, n_ord // 100), trigger=("special", "requests"),
        trigger_frac=0.01,
    )
    tables["orders"] = {
        "o_orderkey": o_orderkey,
        "o_custkey": o_custkey,
        "o_orderstatus": np.empty(n_ord, dtype=object),  # filled below
        "o_totalprice": np.zeros(n_ord, dtype=np.int64),  # filled below
        "o_orderdate": o_orderdate.astype(np.int32),
        "o_orderpriority": _pool(PRIORITIES)[
            rng.choice(len(PRIORITIES), n_ord)],
        "o_clerk": _pool([f"Clerk#{i:09d}"
                          for i in range(max(n_ord // 1000, 2))])[
            rng.integers(1, max(n_ord // 1000, 2), n_ord)],
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": o_comment_pool[rng.integers(0, len(o_comment_pool), n_ord)],
    }
    types["o_orderdate"] = ("date",)
    types["o_totalprice"] = ("decimal", 15, 2)

    # ---- lineitem -------------------------------------------------------
    n_lines = rng.integers(1, 8, n_ord)
    n_li = int(n_lines.sum())
    l_orderkey = np.repeat(o_orderkey, n_lines)
    l_odate = np.repeat(o_orderdate, n_lines)
    l_linenumber = (np.arange(n_li) -
                    np.repeat(np.cumsum(n_lines) - n_lines, n_lines) + 1)
    l_partkey = rng.integers(1, max(n_part, 2), n_li, dtype=np.int64)
    # supplier consistent with partsupp: one of the 4 suppliers of the part
    j = rng.integers(0, 4, n_li)
    l_suppkey = ((l_partkey + j * ((n_supp // 4) + 1)) % n_supp + 1).astype(np.int64)
    l_quantity = rng.integers(1, 51, n_li, dtype=np.int64) * 100  # scale 2
    l_extendedprice = (l_quantity // 100) * p_retail[l_partkey - 1]
    l_discount = rng.integers(0, 11, n_li, dtype=np.int64)  # scale 2: 0.00-0.10
    l_tax = rng.integers(0, 9, n_li, dtype=np.int64)
    l_shipdate = l_odate + rng.integers(1, 122, n_li)
    l_commitdate = l_odate + rng.integers(30, 91, n_li)
    l_receiptdate = l_shipdate + rng.integers(1, 31, n_li)
    l_linestatus = _pool(["F", "O"])[(l_shipdate > _CURRENT).astype(np.intp)]
    rf = rng.integers(0, 2, n_li)
    l_returnflag = _pool(["R", "A", "N"])[np.where(
        l_receiptdate <= _CURRENT, rf != 0, 2).astype(np.intp)]
    tables["lineitem"] = {
        "l_orderkey": l_orderkey,
        "l_partkey": l_partkey,
        "l_suppkey": l_suppkey,
        "l_linenumber": l_linenumber.astype(np.int64),
        "l_quantity": l_quantity,
        "l_extendedprice": l_extendedprice,
        "l_discount": l_discount,
        "l_tax": l_tax,
        "l_returnflag": l_returnflag,
        "l_linestatus": l_linestatus,
        "l_shipdate": l_shipdate.astype(np.int32),
        "l_commitdate": l_commitdate.astype(np.int32),
        "l_receiptdate": l_receiptdate.astype(np.int32),
        "l_shipinstruct": _pool(SHIPINSTRUCT)[
            rng.choice(len(SHIPINSTRUCT), n_li)],
        "l_shipmode": _pool(SHIPMODES)[rng.choice(len(SHIPMODES), n_li)],
        "l_comment": _comment_pool(rng, 500)[rng.integers(0, 500, n_li)],
    }
    for c in ("l_quantity", "l_extendedprice"):
        types[c] = ("decimal", 15, 2)
    types["l_discount"] = ("decimal", 15, 2)
    types["l_tax"] = ("decimal", 15, 2)
    for c in ("l_shipdate", "l_commitdate", "l_receiptdate"):
        types[c] = ("date",)

    # back-fill orders totals/status from lineitem
    disc_price = l_extendedprice * (100 - l_discount) // 100
    charged = disc_price * (100 + l_tax) // 100
    o_total = np.zeros(n_ord + 1, dtype=np.int64)
    np.add.at(o_total, l_orderkey, charged)
    tables["orders"]["o_totalprice"] = o_total[1:]
    all_f = np.ones(n_ord + 1, dtype=bool)
    any_f = np.zeros(n_ord + 1, dtype=bool)
    isf = l_shipdate <= _CURRENT
    np.logical_and.at(all_f, l_orderkey, isf)
    np.logical_or.at(any_f, l_orderkey, isf)
    tables["orders"]["o_orderstatus"] = _pool(["F", "P", "O"])[np.where(
        all_f[1:], 0, np.where(any_f[1:], 1, 2)).astype(np.intp)]

    return tables, types
