"""TPC-H 22-query result parity vs the SQLite oracle (SURVEY §4 tier 4).

Scale factor via TPCH_SF (default 0.01 for the CI-speed suite; the
round evidence runs TPCH_SF=1 — see SF1_PARITY artifacts)."""

import os

import numpy as np
import pytest

from oceanbase_tpu.bench.oracle import load_sqlite, rows_match, run_oracle
from oceanbase_tpu.bench.tpch import TPCH_PRIMARY_KEYS, gen_tpch
from oceanbase_tpu.bench.tpch_queries import QUERIES

SF = float(os.environ.get("TPCH_SF", "0.01"))


@pytest.fixture(scope="module")
def env(new_module_session):
    tables, types = gen_tpch(sf=SF)
    sess = new_module_session()
    for name, arrays in tables.items():
        sess.catalog.load_numpy(
            name, arrays,
            types={k: v for k, v in types.items() if k in arrays},
            primary_key=TPCH_PRIMARY_KEYS[name],
        )
    conn = load_sqlite(tables, types)
    return sess, conn


@pytest.mark.parametrize("qnum", sorted(QUERIES))
def test_tpch_query(env, qnum):
    sess, conn = env
    sql = QUERIES[qnum]
    want = run_oracle(conn, sql)
    got = sess.execute(sql).rows()
    ordered = "order by" in sql.lower() and qnum not in (2, 18, 21)
    ok, why = rows_match(got, want, ordered=ordered)
    assert ok, f"Q{qnum}: {why}\n got[:3]={got[:3]}\nwant[:3]={want[:3]}"


def test_the_fixture_runs_under_the_plan_cache(env):
    """The session these statements run in is a Database's: the second
    execution of a statement is a plan-cache hit, as in the cells."""
    from oceanbase_tpu.server import metrics as qmetrics

    sess, _conn = env
    sess.execute(QUERIES[6])
    hits = qmetrics.counter_value("plan_cache.hits")
    misses = qmetrics.counter_value("plan_cache.misses")
    sess.execute(QUERIES[6])
    assert qmetrics.counter_value("plan_cache.hits") == hits + 1
    assert qmetrics.counter_value("plan_cache.misses") == misses
