"""Scalar function library tests vs SQLite/numpy oracles."""

import math
import sqlite3

import numpy as np
import pytest


@pytest.fixture(scope="module")
def env(new_module_session):
    rng = np.random.default_rng(3)
    n = 200
    a = rng.integers(-50, 50, n)
    f = rng.uniform(-5, 5, n)
    words = rng.choice(np.array(["  hello ", "World", "abcdef", "x"]), n)
    s = new_module_session()
    s.catalog.load_numpy("t", {"a": a, "f": f, "w": words})
    conn = sqlite3.connect(":memory:")
    conn.create_function("ln", 1, math.log)
    # sign()/mod() are native only from sqlite 3.35; UDFs keep old oracles
    conn.create_function(
        "sign", 1,
        lambda x: None if x is None else (x > 0) - (x < 0))
    conn.create_function(
        "mod", 2,
        lambda x, y: None if x is None or y is None else math.fmod(x, y))
    conn.execute("create table t (a, f, w)")
    conn.executemany("insert into t values (?,?,?)",
                     list(zip(a.tolist(), f.tolist(), words.tolist())))
    return s, conn


def _both(env, sql, rel=1e-9):
    s, conn = env
    got = sorted(s.execute(sql).rows())
    want = sorted(tuple(r) for r in conn.execute(sql).fetchall())
    assert len(got) == len(want), sql
    for g, w in zip(got, want):
        for x, y in zip(g, w):
            if isinstance(x, float) or isinstance(y, float):
                assert x == pytest.approx(y, rel=rel), sql
            else:
                assert x == y, sql


def test_math_functions(env):
    _both(env, "select a, abs(a), sign(a) from t")
    _both(env, "select f, round(f, 2) from t", rel=1e-6)
    _both(env, "select a, mod(a, 7) from t")
    _both(env, "select ln(abs(a) + 1) from t")
    s, _ = env
    r = s.execute("select ceil(2.3) as c, floor(2.7) as fl, "
                  "power(2, 10) as p, sqrt(16.0) as q").rows()
    assert r == [(3, 2, 1024.0, 4.0)]


def test_string_functions(env):
    _both(env, "select w, length(w), trim(w), ltrim(w), rtrim(w), "
               "replace(w, 'l', 'L') from t")
    s, _ = env
    r = s.execute("select upper(trim(w)) as u from t where w = 'x'").rows()
    assert all(x == ("X",) for x in r)
    r = s.execute("select concat(trim(w), '!') as c from t limit 1").rows()
    assert r[0][0].endswith("!")
    r = s.execute("select left(w, 2) as l, right(w, 2) as r, "
                  "reverse(w) as v from t where w = 'World'").rows()
    assert r[0] == ("Wo", "ld", "dlroW")


def test_null_functions(env):
    s, _ = env
    s.catalog.load_numpy("nn", {"x": np.array([1, 2, 3])},
                         valids={"x": np.array([True, False, True])})
    r = s.execute("select ifnull(x, -1) as v from nn order by v").rows()
    assert r == [(-1,), (1,), (3,)]
    r = s.execute("select nullif(x, 1) as v from nn order by x").rows()
    assert r == [(None,), (None,), (3,)]
    r = s.execute("select greatest(x, 2) as g, least(x, 2) as l "
                  "from nn where x = 3").rows()
    assert r == [(3, 2)]


def test_date_functions(new_session):
    s = new_session()
    from oceanbase_tpu.datatypes import SqlType, date_to_days

    days = np.array([date_to_days(x) for x in
                     ["1994-03-15", "1996-12-31", "2000-02-29"]])
    s.catalog.load_numpy("d", {"dt": days}, types={"dt": SqlType.date()})
    r = s.execute("select quarter(dt) as q, dayofyear(dt) as dy, "
                  "dayofweek(dt) as dw from d order by dt").rows()
    assert r[0] == (1, 74, 3)     # 1994-03-15 was a Tuesday (dow=3)
    assert r[1][0] == 4 and r[1][1] == 366  # 1996 is a leap year
    r = s.execute("select datediff(dt, date '1994-01-01') as dd "
                  "from d order by dt limit 1").rows()
    assert r == [(73,)]
    # add_months through non-literal date arithmetic (device path)
    r = s.execute("select add_months(dt, 12) as nx from d order by dt"
                  ).rows()
    assert r[0][0] == "1995-03-15"
    assert r[2][0] == "2001-02-28"  # leap-day clamp


def test_extended_function_batch(new_session):
    """Round-4 function-surface widening (≙ src/sql/engine/expr breadth:
    string pad/search, math, conditional, date-name functions)."""
    import numpy as np

    s = new_session()
    s.catalog.load_numpy(
        "fx", {"k": np.arange(3),
               "s": np.array(["abc", "hello world", ""], dtype=object),
               "d": np.array([19723, 19754, 19783], dtype=np.int64)},
        primary_key=["k"])
    cases = [
        ("select lpad(s, 5, '*') from fx order by k",
         ["**abc", "hello", "*****"]),
        ("select repeat(s, 2) from fx order by k",
         ["abcabc", "hello worldhello world", ""]),
        ("select instr(s, 'l') from fx order by k", [0, 3, 0]),
        ("select substring_index(s, ' ', 1) from fx order by k",
         ["abc", "hello", ""]),
        ("select if(k = 1, upper(s), s) from fx order by k",
         ["abc", "HELLO WORLD", ""]),
        ("select isnull(s) from fx order by k", [0, 0, 0]),
        ("select sign(k - 1) from fx order by k", [-1, 0, 1]),
    ]
    for sql, exp in cases:
        got = [r[0] for r in s.execute(sql).rows()]
        assert got == exp, (sql, got, exp)
    # float math
    got = s.execute("select degrees(pi()), log(2, 8.0), "
                    "round(atan2(1.0, 1.0), 4) from fx limit 1").rows()[0]
    assert abs(got[0] - 180.0) < 1e-9 and abs(got[1] - 3.0) < 1e-9
    # date names: day 19723 = 2024-01-01, a Monday
    got = s.execute("select dayname(d), monthname(d) from fx "
                    "order by k limit 1").rows()[0]
    assert got == ("Monday", "January")
    # md5 is the real digest
    import hashlib

    got = s.execute("select md5(s) from fx order by k limit 1").rows()[0][0]
    assert got == hashlib.md5(b"abc").hexdigest()


def test_concat_ws_skips_nulls(new_session):
    """MySQL CONCAT_WS semantics: NULL values are skipped with their
    separator (unlike CONCAT's null propagation)."""
    import numpy as np

    s = new_session()
    s.catalog.load_numpy(
        "cw", {"k": np.arange(3),
               "a": np.array(["x", "y", "z"], dtype=object),
               "b": np.array(["1", "", "3"], dtype=object)},
        valids={"b": np.array([True, False, True])},
        primary_key=["k"])
    r = s.execute("select concat_ws('-', a, b) from cw order by k")
    assert [x[0] for x in r.rows()] == ["x-1", "y", "z-3"]
