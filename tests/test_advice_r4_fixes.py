"""Regression tests for the round-3 advisor findings (ADVICE.md r3) and
the VERDICT r3 #7 spill-tier reachability holes.

≙ the reference's regression suite discipline: every review finding gets
a pinned test (SURVEY §4).
"""

import numpy as np
import pytest

from oceanbase_tpu.server import Database

N = 40_000


def _mk(tmp_path, budget=4096):
    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute(f"alter system set sql_work_area_rows = {budget}")
    return db, s


def _load_big(s, name="t", n=N, seed=1):
    rng = np.random.default_rng(seed)
    v = rng.integers(0, 1_000_000, n)
    g = rng.integers(0, n // 2, n)
    s.execute(f"create table {name} "
              f"(k int primary key, v int, g int)")
    rows = ", ".join(f"({i}, {v[i]}, {g[i]})" for i in range(n))
    s.execute(f"insert into {name} values {rows}")
    return v, g


# ---------------------------------------------------------------------------
# ADVICE r3 medium: _stream_join per-batch capacity must scale with the
# batch, not the planner's whole-query estimate
# ---------------------------------------------------------------------------

def test_stream_join_batch_capacity_ignores_plan_estimate(tmp_path):
    from oceanbase_tpu.exec import spill_exec

    db, s = _mk(tmp_path)
    _load_big(s)
    s.execute("create table d (g int primary key, name varchar(16))")
    s.execute("insert into d values " + ", ".join(
        f"({i}, 'n{i % 7}')" for i in range(0, N // 2, 16)))

    caps = []
    orig = spill_exec.ops.join

    def spy(left, right, lk, rk, **kw):
        caps.append(kw.get("out_capacity"))
        return orig(left, right, lk, rk, **kw)

    spill_exec.ops.join, _saved = spy, orig
    try:
        r = s.execute("select count(*) from t join d on t.g = d.g")
        assert r.rows()[0][0] > 0
    finally:
        spill_exec.ops.join = _saved
    assert s._last_spill is not None and "join" in s._last_spill.kind
    assert caps, "streamed join never reached ops.join"
    # chunk size is spill_exec.DEFAULT_CHUNK_ROWS; first-attempt caps must
    # be O(batch), nowhere near the whole-join estimate (~N rows)
    bound = 4 * spill_exec.DEFAULT_CHUNK_ROWS
    assert all(c is None or c <= bound for c in caps), caps
    db.close()


# ---------------------------------------------------------------------------
# ADVICE r3 low: _materialize_host must surface a dropped output column
# ---------------------------------------------------------------------------

def test_materialize_host_raises_on_missing_column(tmp_path):
    db, s = _mk(tmp_path)
    with pytest.raises(KeyError):
        s._materialize_host(
            {"c1": np.arange(4)}, {}, {}, [("c1", "a"), ("c2", "b")])
    db.close()


# ---------------------------------------------------------------------------
# ADVICE r3 low: selective indexed queries keep the in-memory fast path
# even when the raw table is over budget
# ---------------------------------------------------------------------------

def test_selective_pk_query_skips_spill(tmp_path):
    db, s = _mk(tmp_path)
    _load_big(s)
    s._last_spill = None
    r = s.execute("select v from t where k = 17")
    assert len(r.rows()) == 1
    assert s._last_spill is None, \
        "point lookup on an over-budget table must not stream the table"
    # whole-table scan still spills
    r = s.execute("select k from t order by v limit 3")
    assert s._last_spill is not None
    db.close()


# ---------------------------------------------------------------------------
# ADVICE r3 low: one read point across big streams and small device tables
# ---------------------------------------------------------------------------

def test_spilled_join_reads_small_table_at_one_snapshot(tmp_path):
    db, s = _mk(tmp_path)
    _load_big(s)
    s.execute("create table d (g int primary key, name varchar(16))")
    s.execute("insert into d values " + ", ".join(
        f"({i}, 'n{i % 7}')" for i in range(0, N // 2, 16)))

    snaps = []
    orig = s.catalog.table_data_at

    def spy(name, snapshot, tx_id=0):
        snaps.append((name, snapshot))
        return orig(name, snapshot, tx_id)

    s.catalog.table_data_at = spy
    try:
        s.execute("select count(*) from t join d on t.g = d.g")
    finally:
        s.catalog.table_data_at = orig
    assert s._last_spill is not None
    small_reads = [sn for nm, sn in snaps if nm == "d"]
    assert small_reads, "small side must be read via the snapshot API"
    db.close()


# ---------------------------------------------------------------------------
# VERDICT r3 #7: spill inside explicit transactions
# ---------------------------------------------------------------------------

def test_spill_works_inside_transaction_for_clean_tables(tmp_path):
    db, s = _mk(tmp_path)
    v, _g = _load_big(s)
    s.execute("begin")
    r = s.execute("select count(*), sum(v) from t")
    cnt, sv = r.rows()[0]
    assert cnt == N and sv == int(v.sum())
    assert s._last_spill is not None, \
        "over-budget query inside a tx must still reach the disk tier"
    s.execute("commit")
    db.close()


def test_spill_skipped_for_tables_written_by_the_tx(tmp_path):
    db, s = _mk(tmp_path)
    v, _g = _load_big(s)
    s.execute("begin")
    s.execute("insert into t values (999999, 1, 1)")
    s._last_spill = None
    r = s.execute("select count(*) from t")
    # own write must be visible -> in-memory own-writes path, no spill
    assert r.rows()[0][0] == N + 1
    assert s._last_spill is None
    s.execute("rollback")
    db.close()


def test_tx_snapshot_isolation_through_spill_tier(tmp_path):
    db, s = _mk(tmp_path)
    v, _g = _load_big(s)
    s.execute("begin")
    r = s.execute("select count(*) from t")
    assert r.rows()[0][0] == N
    # a concurrent session commits new rows mid-transaction
    s2 = db.session()
    s2.execute("insert into t values (888888, 5, 5)")
    # the tx's spilled reads stay at its begin snapshot
    r = s.execute("select count(*) from t")
    assert r.rows()[0][0] == N
    s.execute("commit")
    r = s.execute("select count(*) from t")
    assert r.rows()[0][0] == N + 1
    db.close()


def test_nested_scalar_subquery_filter_not_dropped(new_session):
    """TPC-H Q20 shape: a correlated scalar comparison nested inside an
    IN-subquery must filter the SAME rows the sibling IN predicate
    filters — the decorrelation used to drop the comparison entirely
    (SF1 parity Q20 off-by-one)."""
    import numpy as np

    s = new_session()
    s.catalog.load_numpy("supplier", {
        "s_suppkey": np.array([1, 2]),
        "s_name": np.array(["sup1", "sup2"], dtype=object)},
        primary_key=["s_suppkey"])
    s.catalog.load_numpy("partsupp", {
        "ps_partkey": np.array([10, 20, 30]),
        "ps_suppkey": np.array([1, 1, 2]),
        "ps_availqty": np.array([1, 1000, 1000])}, primary_key=[])
    s.catalog.load_numpy("part", {
        "p_partkey": np.array([10, 30]),
        "p_name": np.array(["forest a", "forest b"], dtype=object)},
        primary_key=["p_partkey"])
    s.catalog.load_numpy("lineitem", {
        "l_partkey": np.array([10, 20, 30]),
        "l_suppkey": np.array([1, 1, 2]),
        "l_quantity": np.array([100.0, 1.0, 4.0])}, primary_key=[])
    r = s.execute(
        "select s_name from supplier where s_suppkey in ("
        " select ps_suppkey from partsupp"
        " where ps_partkey in (select p_partkey from part"
        "                      where p_name like 'forest%')"
        "   and ps_availqty > (select 0.5 * sum(l_quantity)"
        "                      from lineitem"
        "                      where l_partkey = ps_partkey"
        "                        and l_suppkey = ps_suppkey)"
        ") order by s_name")
    assert r.rows() == [("sup2",)]
