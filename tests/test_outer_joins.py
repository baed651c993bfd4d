"""RIGHT/FULL OUTER join parity vs the SQLite oracle (VERDICT r3 #5).

≙ src/sql/engine/join/hash_join/ob_hash_join_vec_op.h:342 (unmatched-
build FILL_RIGHT emission) — here the full-outer lowering appends one
lane per build row after the probe expansion.
"""

import numpy as np
import pytest

from oceanbase_tpu.bench.oracle import rows_match


@pytest.fixture(scope="module")
def env(new_module_session):
    import sqlite3

    rng = np.random.default_rng(3)
    na, nb = 300, 200
    a = {"ak": np.arange(na), "aj": rng.integers(0, 80, na),
         "av": rng.integers(0, 1000, na)}
    b = {"bk": np.arange(nb), "bj": rng.integers(40, 120, nb),
         "bv": rng.integers(0, 1000, nb)}
    sess = new_module_session()
    sess.catalog.load_numpy("a", a, primary_key=["ak"])
    sess.catalog.load_numpy("b", b, primary_key=["bk"])
    conn = sqlite3.connect(":memory:")
    for nm, cols in (("a", a), ("b", b)):
        conn.execute(f"create table {nm} ({', '.join(cols)})")
        conn.executemany(
            f"insert into {nm} values ({','.join('?' * len(cols))})",
            list(zip(*[c.tolist() for c in cols.values()])))
    return sess, conn


# sqlite grew RIGHT/FULL OUTER JOIN in 3.39; older oracles get the
# rewritten equivalent from conftest
from conftest import rewrite_outer_join_for_old_sqlite


def _oracle_sql(sql: str) -> str:
    return rewrite_outer_join_for_old_sqlite(
        sql, "a", "b", ("ak", "aj", "av"), ("bk", "bj", "bv"))


QUERIES = [
    "select ak, aj, bk, bj from a full outer join b on aj = bj "
    "order by ak, bk",
    "select count(*), sum(av), sum(bv) from a full outer join b "
    "on aj = bj",
    "select ak, bk from a right outer join b on aj = bj order by bk, ak",
    "select count(*) from a right join b on aj = bj",
    # full outer + aggregation over the null-extended side
    "select bj, count(ak) from a full outer join b on aj = bj "
    "group by bj order by bj",
    # full outer with no matches at all on one side
    "select count(*) from a full outer join b on av = bk + 5000",
]


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_outer_join_parity(env, qi):
    sess, conn = env
    sql = QUERIES[qi]
    want = [tuple(r) for r in conn.execute(_oracle_sql(sql)).fetchall()]
    got = sess.execute(sql).rows()
    ok, why = rows_match(got, want, ordered="order by" in sql)
    assert ok, f"{sql}\n{why}\n got={got[:5]}\nwant={want[:5]}"


def test_full_outer_distributes_on_px(env):
    sess, _conn = env
    sql = ("select count(*), sum(av), sum(bv) from a full outer join b "
           "on aj = bj")
    serial = sess.execute(sql).rows()
    sess.variables["px_dop"] = 8
    try:
        dist = sess.execute(sql).rows()
        assert sess._last_px, "full outer should distribute via HASH-HASH"
    finally:
        sess.variables["px_dop"] = 0
    assert serial == dist
