"""Top-N fusion + plan cache tests."""

import numpy as np
import pytest

from oceanbase_tpu.server import Database


def test_topn_matches_numpy_oracle(rng, new_session):
    n = 20000
    a = rng.integers(-1000, 1000, n)
    f = rng.random(n)
    sv = rng.choice(np.array(["aa", "bb", "cc", "dd"]), n)
    s = new_session()
    s.catalog.load_numpy("t", {"a": a, "f": f, "s": sv})
    got = [r[0] for r in s.execute(
        "select a from t order by a limit 7").rows()]
    assert got == sorted(a.tolist())[:7]
    got = [r[0] for r in s.execute(
        "select a from t order by a desc limit 7").rows()]
    assert got == sorted(a.tolist(), reverse=True)[:7]
    got = [r[0] for r in s.execute(
        "select f from t order by f desc limit 5").rows()]
    np.testing.assert_allclose(got, np.sort(f)[::-1][:5])
    got = [r[0] for r in s.execute(
        "select s from t order by s limit 4").rows()]
    assert got == sorted(sv.tolist())[:4]
    # filtered top-N: dead rows must never crowd out live ones
    got = s.execute("select a from t where a > 900 order by a desc limit 10"
                    ).rows()
    want = sorted([x for x in a.tolist() if x > 900], reverse=True)[:10]
    assert [r[0] for r in got] == want


def test_topn_null_desc_with_filter(new_session):
    # live NULLs under DESC must outrank dead (filtered) rows
    s = new_session()
    s.catalog.load_numpy(
        "t", {"x": np.array([10, 500, 0, 0]),
              "flt": np.array([1, 0, 1, 1])},
        valids={"x": np.array([True, True, False, False])})
    r = s.execute("select x from t where flt = 1 order by x desc limit 3"
                  ).rows()
    assert r == [(10,), (None,), (None,)]


def test_topn_with_nulls(new_session):
    s = new_session()
    s.catalog.load_numpy("t", {"x": np.array([5, 1, 9, 3])},
                         valids={"x": np.array([True, False, True, True])})
    r = s.execute("select x from t order by x limit 2").rows()
    assert r == [(None,), (3,)]  # nulls first under ASC
    r = s.execute("select x from t order by x desc limit 2").rows()
    assert r == [(9,), (5,)]


def test_plan_cache_hit_and_invalidation(tmp_path):
    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table t (k int primary key, v int)")
    s.execute("insert into t values (1, 10), (2, 20)")
    q = "select sum(v) from t where k >= ?"
    assert s.execute(q, params=[1]).rows() == [(30,)]
    n_entries = len(s.plan_cache)
    assert n_entries >= 1
    # same text+params hits the cache (no growth)
    assert s.execute(q, params=[1]).rows() == [(30,)]
    assert len(s.plan_cache) == n_entries
    # data changes flow through a cached plan
    s.execute("insert into t values (3, 5)")
    assert s.execute(q, params=[1]).rows() == [(35,)]
    # schema change invalidates (new key -> rebind)
    s.execute("create table u (z int)")
    assert s.execute(q, params=[1]).rows() == [(35,)]
    db.close()
