"""Exact NumPy reference for TPC-H Q13 (Customer Distribution) at any
WORD1 / WORD2: for every customer the number of its orders whose comment
does NOT match ``%WORD1%WORD2%`` (``bincount`` of those orders' customer
keys, read at EVERY customer's key, so a customer without such an order
counts 0 and ``c_count = 0`` is a group), then how many customers have
each count (``bincount`` of that), ordered by that number descending and
the count descending.  Imports nothing of the program.

The pattern is evaluated once a DISTINCT comment: the generator draws
comments from a pool, so the column's 15M entries at SF10 are pointers to
150,000 strings, told apart here by identity (two equal strings that are
not one object are only matched twice).  ``%A%B%`` matches where ``B``
occurs after the end of the first ``A``.

Compared bit for bit and in order: every value is an integer.  What the
comparison catches (shown in ``benchmark/tests/drive_q18_faults.py``): an
outer join that drops the customers without orders loses the whole row
``c_count = 0`` (a third of the customers); ``count(*)`` for
``count(o_orderkey)`` counts the NULL-extended row of such a customer as
one order, so that row's customers are added to ``c_count = 1``.
"""

import numpy as np


def _matches(comments: np.ndarray, word1: str, word2: str) -> np.ndarray:
    """Per row: does the comment match ``%word1%word2%``?"""
    ids = np.fromiter(map(id, comments), dtype=np.int64, count=len(comments))
    _, first, inverse = np.unique(ids, return_index=True, return_inverse=True)

    def one(s: str) -> bool:
        i = s.find(word1)
        return i >= 0 and s.find(word2, i + len(word1)) >= 0

    hit = np.fromiter((one(str(s)) for s in comments[first]), dtype=bool,
                      count=len(first))
    return hit[inverse]


def answer(tables: dict, params: dict) -> list:
    cust, orders = tables["customer"], tables["orders"]
    counted = ~_matches(orders["o_comment"], params["WORD1"],
                        params["WORD2"])
    ckey = cust["c_custkey"].astype(np.int64)
    ocust = orders["o_custkey"].astype(np.int64)
    per_key = np.bincount(ocust[counted],
                          minlength=int(max(ckey.max(), ocust.max())) + 1)
    c_count = per_key[ckey]              # one a customer, 0 included
    custdist = np.bincount(c_count)
    rows = [(int(c), int(n)) for c, n in enumerate(custdist) if n]
    return sorted(rows, key=lambda r: (-r[1], -r[0]))


def extract(names: list, arrays: dict) -> list:
    return [(int(c), int(n)) for c, n in zip(arrays["c_count"],
                                             arrays["custdist"])]
