"""Window function tests vs the SQLite oracle (≙ window-function op tests)."""

import sqlite3

import numpy as np
import pytest


@pytest.fixture(scope="module")
def env(new_module_session, rng=np.random.default_rng(7)):
    n = 500
    dept = rng.integers(0, 5, n)
    sal = rng.integers(1000, 9000, n)
    emp = np.arange(n)
    sess = new_module_session()
    sess.catalog.load_numpy("emp", {"eid": emp, "dept": dept, "sal": sal})
    conn = sqlite3.connect(":memory:")
    conn.execute("create table emp (eid, dept, sal)")
    conn.executemany("insert into emp values (?,?,?)",
                     list(zip(emp.tolist(), dept.tolist(), sal.tolist())))
    return sess, conn


def _both(env, sql):
    sess, conn = env
    got = sorted(sess.execute(sql).rows())
    want = sorted(tuple(r) for r in conn.execute(sql).fetchall())
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            if isinstance(a, float) or isinstance(b, float):
                assert a == pytest.approx(b, rel=1e-9)
            else:
                assert a == b


def test_row_number(env):
    _both(env, "select eid, row_number() over "
               "(partition by dept order by sal desc, eid) as rn from emp")


def test_rank_dense_rank(env):
    _both(env, "select eid, rank() over (partition by dept order by sal) as r, "
               "dense_rank() over (partition by dept order by sal) as dr "
               "from emp")


def test_partition_aggregates(env):
    _both(env, "select eid, sum(sal) over (partition by dept) as total, "
               "count(*) over (partition by dept) as cnt, "
               "max(sal) over (partition by dept) as mx from emp")


def test_running_aggregates(env):
    _both(env, "select eid, sum(sal) over "
               "(partition by dept order by eid) as running from emp")
    # RANGE-frame peers: ties on the order key share values
    _both(env, "select eid, sum(sal) over "
               "(partition by dept order by sal) as running, "
               "min(sal) over (partition by dept order by eid) as rmin "
               "from emp")


def test_window_no_partition(env):
    _both(env, "select eid, avg(sal) over () as a, "
               "row_number() over (order by eid) as rn from emp")


def test_window_over_groupby(env):
    _both(env, "select dept, sum(sal) as s, "
               "rank() over (order by sum(sal) desc) as r "
               "from emp group by dept")
