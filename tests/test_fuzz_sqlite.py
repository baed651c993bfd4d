"""Differential fuzzer: random queries diffed against SQLite.

≙ the reference's mysqltest result-diff philosophy, randomized: generate
projection/filter/join/aggregate/order-by combinations over typed tables
and require row-for-row agreement with SQLite.  Seeds are fixed so
failures reproduce.
"""

import sqlite3

import numpy as np
import pytest


N_QUERIES = 60


@pytest.fixture(scope="module")
def env(new_module_session):
    rng = np.random.default_rng(11)
    n1, n2 = 400, 120
    t1 = {
        "a": rng.integers(-20, 20, n1),
        "b": rng.integers(0, 8, n1),
        "f": np.round(rng.uniform(-10, 10, n1), 3),
        "s": rng.choice(np.array(["red", "green", "blue", "teal"]), n1),
    }
    nulls = rng.random(n1) < 0.15
    t2 = {
        "x": rng.integers(0, 8, n2),
        "y": rng.integers(-5, 5, n2),
        "w": rng.choice(np.array(["red", "blue", "pink"]), n2),
    }
    s = new_module_session()
    s.catalog.load_numpy("t1", t1, valids={"b": ~nulls})
    s.catalog.load_numpy("t2", t2)
    conn = sqlite3.connect(":memory:")
    conn.execute("create table t1 (a, b, f, s)")
    conn.executemany(
        "insert into t1 values (?,?,?,?)",
        [(int(t1["a"][i]), None if nulls[i] else int(t1["b"][i]),
          float(t1["f"][i]), str(t1["s"][i])) for i in range(n1)])
    conn.execute("create table t2 (x, y, w)")
    conn.executemany("insert into t2 values (?,?,?)",
                     list(zip(t2["x"].tolist(), t2["y"].tolist(),
                              t2["w"].tolist())))
    # MySQL functions SQLite lacks: give the oracle reference impls
    conn.create_function("repeat", 2, lambda s_, n: None if s_ is None
                         else str(s_) * max(int(n), 0))
    conn.create_function(
        "lpad", 3, lambda s_, n, p: None if s_ is None else
        (str(s_)[:n] if len(str(s_)) >= n
         else (str(p) * n)[: n - len(str(s_))] + str(s_)))
    conn.create_function(
        "concat_ws", -1,
        lambda sep, *xs: sep.join(str(x) for x in xs if x is not None))
    conn.create_function("isnull", 1, lambda x: 1 if x is None else 0)
    conn.create_function("if", 3, lambda c, a, b: a if c else b)
    conn.create_function(
        "substring_index", 3, lambda s_, d, k: None if s_ is None else
        (d.join(str(s_).split(d)[:k]) if k >= 0
         else d.join(str(s_).split(d)[k:])))
    return s, conn


from conftest import rewrite_outer_join_for_old_sqlite


def _oracle_sql(sql: str) -> str:
    return rewrite_outer_join_for_old_sqlite(
        sql, "t1", "t2", ("a", "b", "f", "s"), ("x", "y", "w"))


def _gen_query(rng) -> str:
    preds = [
        "a > 0", "a between -5 and 10", "b = 3", "b is null",
        "b is not null", "s = 'red'", "s in ('red', 'blue')",
        "s like '%e%'", "f < 2.5", "a % 3 = 0", "abs(a) > 10",
        "not (a > 0)", "a > 0 or b = 2", "length(s) = 4",
    ]
    aggs = ["count(*)", "sum(a)", "min(f)", "max(a)", "avg(a)", "count(b)"]
    shape = rng.integers(0, 10)
    where = ""
    if rng.random() < 0.8:
        k = int(rng.integers(1, 3))
        chosen = list(rng.choice(preds, k, replace=False))
        where = " where " + " and ".join(chosen)
    if shape == 0:      # projection + filter + order
        return f"select a, b, s from t1{where} order by a, b, s, f"
    if shape == 1:      # scalar aggregates
        k = int(rng.integers(1, 4))
        cols = ", ".join(f"{a} as c{i}"
                         for i, a in enumerate(rng.choice(aggs, k,
                                                          replace=False)))
        return f"select {cols} from t1{where}"
    if shape == 2:      # group by
        agg = rng.choice(aggs)
        return (f"select b, {agg} as agg1 from t1{where} "
                f"group by b order by b")
    if shape == 3:      # window functions
        wf = rng.choice([
            "row_number() over (partition by s order by a, f)",
            "rank() over (partition by b order by a)",
            "sum(a) over (partition by s)",
            "count(*) over (partition by b order by a, f)",
        ])
        return f"select a, s, {wf} as w from t1{where} order by a, f, s"
    if shape == 4:      # CTE + derived table
        return (f"with base as (select a, b, s from t1{where}) "
                f"select s, count(*) as n from base group by s order by s")
    if shape == 5:      # set operation
        op = rng.choice(["union", "union all", "except", "intersect"])
        return (f"select b from t1{where} {op} "
                f"select x from t2 order by 1")
    if shape == 6:      # join + aggregate
        return (f"select s, count(*) as n, sum(y) as sy from t1, t2 "
                f"where b = x{' and ' + rng.choice(preds) if rng.random() < 0.5 else ''} "
                f"group by s order by s")
    if shape == 7:      # outer joins (round 4: RIGHT/FULL)
        kind = rng.choice(["left", "right", "full outer"])
        return (f"select a, b, x, y from t1 {kind} join t2 on b = x"
                f"{where} order by a, b, x, y")
    if shape == 8:      # round-4 window functions + ROWS frames
        wf = rng.choice([
            "lag(a) over (partition by b order by a, f)",
            "lead(a, 2) over (partition by s order by a, f)",
            "ntile(3) over (order by a, f)",
            "first_value(a) over (partition by s order by a, f)",
            "sum(a) over (partition by s order by a, f "
            "rows between 2 preceding and current row)",
            "min(f) over (partition by b order by a, f "
            "rows between 1 preceding and 1 following)",
        ])
        return f"select a, s, {wf} as w from t1{where} order by a, f, s"
    # round-4 string/conditional functions
    fn = rng.choice([
        "concat_ws('-', s, s)", "if(a > 0, s, 'neg')",
        "instr(s, 'e')", "substring_index(s, 'e', 1)",
        "lpad(s, 6, '*')", "repeat(s, 2)",
    ])
    return f"select a, {fn} as r from t1{where} order by a, s, f"


def _normalize(rows):
    out = []
    for r in rows:
        row = []
        for x in r:
            if isinstance(x, float):
                row.append(round(x, 6))
            else:
                row.append(x)
        out.append(tuple(row))
    return sorted(out, key=lambda t: tuple((v is None, str(type(v)), v)
                                           for v in t))


def test_fuzz_vs_sqlite(env):
    s, conn = env
    rng = np.random.default_rng(99)
    failures = []
    for qi in range(N_QUERIES):
        sql = _gen_query(rng)
        try:
            got = _normalize(s.execute(sql).rows())
            want = _normalize(
                [tuple(r) for r in conn.execute(_oracle_sql(sql))])
        except Exception as e:  # noqa: BLE001
            failures.append((sql, f"exception {type(e).__name__}: {e}"))
            continue
        if len(got) != len(want):
            failures.append((sql, f"rowcount {len(got)} != {len(want)}"))
            continue
        for g, w in zip(got, want):
            ok = len(g) == len(w) and all(
                (a == pytest.approx(b, rel=1e-6)
                 if isinstance(a, float) or isinstance(b, float)
                 else a == b)
                for a, b in zip(g, w)
                if not (a is None and b is None))
            if not ok:
                failures.append((sql, f"row diff: {g} != {w}"))
                break
    assert not failures, "\n".join(f"{q}\n  -> {why}"
                                   for q, why in failures[:5])
