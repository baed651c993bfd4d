"""Window-function completeness: lead/lag/ntile/first_value/last_value
and ROWS frames, diffed against the SQLite oracle (VERDICT r3 item #9).

≙ src/sql/engine/window_function/ob_window_function_vec_op.h coverage.
"""

import numpy as np
import pytest

from oceanbase_tpu.bench.oracle import load_sqlite, rows_match, run_oracle


@pytest.fixture(scope="module")
def env(new_module_session):
    rng = np.random.default_rng(7)
    n = 500
    tables = {
        "t": {
            "k": np.arange(n),
            "g": rng.integers(0, 7, n),
            "v": rng.integers(-50, 100, n),
        }
    }
    # some NULLs in v via a second nullable column
    sess = new_module_session()
    sess.catalog.load_numpy("t", tables["t"], primary_key=["k"])
    conn = load_sqlite(tables, {})
    return sess, conn


QUERIES = [
    # lead/lag with offsets and defaults
    "select k, lag(v) over (partition by g order by k) from t order by k",
    "select k, lead(v) over (partition by g order by k) from t order by k",
    "select k, lead(v, 3) over (partition by g order by k) from t "
    "order by k",
    "select k, lag(v, 2, -1) over (partition by g order by k) from t "
    "order by k",
    # ntile
    "select k, ntile(4) over (order by k) from t order by k",
    "select k, ntile(3) over (partition by g order by k) from t "
    "order by k",
    # first/last value (default frame)
    "select k, first_value(v) over (partition by g order by k) from t "
    "order by k",
    "select k, last_value(v) over (partition by g order by k) from t "
    "order by k",
    # ROWS frames: running and sliding aggregates
    "select k, sum(v) over (partition by g order by k "
    "rows between unbounded preceding and current row) from t order by k",
    "select k, sum(v) over (partition by g order by k "
    "rows between 3 preceding and current row) from t order by k",
    "select k, sum(v) over (partition by g order by k "
    "rows between 2 preceding and 2 following) from t order by k",
    "select k, count(v) over (partition by g order by k "
    "rows between 1 preceding and 1 following) from t order by k",
    "select k, min(v) over (partition by g order by k "
    "rows between 5 preceding and current row) from t order by k",
    "select k, max(v) over (partition by g order by k "
    "rows between 2 preceding and 4 following) from t order by k",
    "select k, avg(v) over (partition by g order by k "
    "rows between 3 preceding and 1 following) from t order by k",
    # frame + navigation combined
    "select k, first_value(v) over (partition by g order by k "
    "rows between 2 preceding and current row) from t order by k",
    "select k, last_value(v) over (partition by g order by k "
    "rows between current row and 2 following) from t order by k",
    # unbounded following side
    "select k, sum(v) over (partition by g order by k "
    "rows between current row and unbounded following) from t "
    "order by k",
]


@pytest.mark.parametrize("qi", range(len(QUERIES)))
def test_window_oracle_parity(env, qi):
    sess, conn = env
    sql = QUERIES[qi]
    want = run_oracle(conn, sql)
    got = sess.execute(sql).rows()
    ok, why = rows_match(got, want, ordered=True)
    assert ok, f"{sql}\n{why}\n got={got[:5]}\nwant={want[:5]}"


def test_window_null_handling(new_session):
    sess = new_session()
    n = 60
    v = np.arange(n, dtype=np.int64)
    valid = (np.arange(n) % 5) != 0
    sess.catalog.load_numpy(
        "tn", {"k": np.arange(n), "g": np.arange(n) % 3, "v": v},
        primary_key=["k"], valids={"v": valid})
    tables = {"tn": {"k": np.arange(n), "g": np.arange(n) % 3,
                     "v": np.where(valid, v, None)}}
    import sqlite3

    conn = sqlite3.connect(":memory:")
    conn.execute("create table tn (k, g, v)")
    conn.executemany("insert into tn values (?,?,?)",
                     list(zip(*[c.tolist()
                                for c in tables["tn"].values()])))
    for sql in (
        "select k, lag(v) over (partition by g order by k) from tn "
        "order by k",
        "select k, sum(v) over (partition by g order by k "
        "rows between 2 preceding and current row) from tn order by k",
        "select k, min(v) over (partition by g order by k "
        "rows between 1 preceding and 1 following) from tn order by k",
    ):
        want = [tuple(r) for r in conn.execute(sql).fetchall()]
        got = sess.execute(sql).rows()
        ok, why = rows_match(got, want, ordered=True)
        assert ok, f"{sql}\n{why}\n got={got[:8]}\nwant={want[:8]}"
