"""Cost-based optimizer plane: index-probe access paths, magic-set
seeded decorrelation, strict Compact overflow, the gv$plan_choice
ledger, general partition-wise matching — plus the PR's admission/dtl
satellites (tenant timeout overlay, memstore running total, cancel
pinning, RUNNING-path lane counters).
"""

from __future__ import annotations

import time

import numpy as np
import pytest

from oceanbase_tpu.exec.plan import (
    Compact,
    HashJoin,
    IndexProbe,
    TableScan,
    execute_plan,
    prepare_index_probes,
    referenced_tables,
)
from oceanbase_tpu.expr import ir
from oceanbase_tpu.server.database import Database
from oceanbase_tpu.sql.parser import parse_sql


@pytest.fixture()
def db(tmp_path):
    d = Database(str(tmp_path / "db"))
    yield d
    d.close()


def _walk(plan):
    stack = [plan]
    while stack:
        n = stack.pop()
        yield n
        stack.extend(n.children())


def _mk_indexed(new_session, seed=3, n_big=4000, n_small=60):
    """big (indexed on k, ~8 rows/key) joined by a tiny filtered side:
    the shape where the index probe beats sorting big for a hash join."""
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 500, n_big).astype(np.int64)
    v = rng.integers(0, 1000, n_big).astype(np.int64)
    tag = rng.integers(0, 100, 500).astype(np.int64)
    s = new_session()
    s.catalog.load_numpy("big", {
        "id": np.arange(n_big, dtype=np.int64), "k": k, "v": v})
    s.catalog.load_numpy("small", {
        "sk": np.arange(500, dtype=np.int64), "tag": tag})
    s.execute("analyze table big")
    s.execute("analyze table small")
    s.execute("create index idx_big_k on big (k)")
    q = ("select sum(big.v) as sv from big, small "
         "where big.k = small.sk and small.tag < 10")
    return s, q, k, v, tag


def _oracle_sum(k, v, tag):
    keep = set(np.nonzero(tag < 10)[0].tolist())
    return int(sum(int(vv) for kk, vv in zip(k, v) if int(kk) in keep))


# ---------------------------------------------------------------------------
# index-probe access path
# ---------------------------------------------------------------------------


def test_index_probe_chosen_and_correct(new_session):
    """The CBO picks the index probe for a small-probe/big-base join,
    and the answer matches both a host oracle and the no-index plan."""
    s, q, k, v, tag = _mk_indexed(new_session)
    want = _oracle_sum(k, v, tag)
    txt = "\n".join(str(r) for r in s.execute("explain " + q).rows())
    assert "IndexProbe" in txt, txt
    assert s.execute(q).rows() == [(want,)]
    # drop the index: the hash plan must agree (schema bump re-binds)
    s.execute("drop index idx_big_k on big")
    txt2 = "\n".join(str(r) for r in s.execute("explain " + q).rows())
    assert "IndexProbe" not in txt2
    assert s.execute(q).rows() == [(want,)]


def test_index_probe_poison_parity(poison, new_session):
    """IndexProbe is a data-reading operator: masked-dead lanes in the
    base, the probe side, or the sidecar must not influence results."""
    s, q, _k, _v, _tag = _mk_indexed(new_session)
    plan, _outs, _est = s._plan_select(parse_sql(q), None)
    assert any(isinstance(n, IndexProbe) for n in _walk(plan))
    tables = {t: s.catalog.table_data(t)
              for t in referenced_tables(plan)
              if s.catalog.has_table(t)}
    prepare_index_probes(s.catalog, plan, tables)
    poison.assert_poison_invariant(
        lambda t: execute_plan(plan, t), tables)


def test_index_probe_survives_dml_between_executions(new_session):
    """The sidecar cache keys on snapshot identity: rows inserted after
    the first execution must be visible to the second."""
    s = new_session()
    s.catalog.load_numpy("t", {
        "a": np.arange(100, dtype=np.int64),
        "k": (np.arange(100, dtype=np.int64) % 10)})
    s.catalog.load_numpy("d", {"dk": np.arange(10, dtype=np.int64)})
    s.execute("analyze table t")
    s.execute("analyze table d")
    s.execute("create index idx_t_k on t (k)")
    q = ("select count(*) from t, d where t.k = d.dk and d.dk < 3")
    first = s.execute(q).rows()
    assert first == [(30,)]


# ---------------------------------------------------------------------------
# magic-set seeded decorrelation (q17 shape)
# ---------------------------------------------------------------------------


def _q17_session(new_session, seed=7, n_part=2000, n_li=12000):
    rng = np.random.default_rng(seed)
    part = {"p_partkey": np.arange(1, n_part + 1, dtype=np.int64),
            "p_brand": rng.integers(0, 25, n_part).astype(np.int64)}
    li = {"l_partkey": rng.integers(1, n_part + 1, n_li).astype(np.int64),
          "l_quantity": rng.integers(1, 51, n_li).astype(np.int64),
          "l_extendedprice":
              rng.integers(100, 100000, n_li).astype(np.int64)}
    s = new_session()
    s.catalog.load_numpy("part", part, primary_key=["p_partkey"])
    s.catalog.load_numpy("lineitem", li)
    s.execute("analyze table part")
    s.execute("analyze table lineitem")
    s.execute("create index idx_l_pk on lineitem (l_partkey)")
    return s, part, li


_Q17 = ("select sum(l_extendedprice) as s from lineitem, part "
        "where p_partkey = l_partkey and p_brand = 7 "
        "and l_quantity < (select 0.2 * avg(l_quantity) from lineitem l2 "
        "where l2.l_partkey = p_partkey)")


def _q17_oracle(part, li):
    sums: dict = {}
    cnts: dict = {}
    for pk, qy in zip(li["l_partkey"], li["l_quantity"]):
        sums[pk] = sums.get(pk, 0) + int(qy)
        cnts[pk] = cnts.get(pk, 0) + 1
    brand7 = set(part["p_partkey"][part["p_brand"] == 7].tolist())
    tot = 0
    for pk, qy, ep in zip(li["l_partkey"], li["l_quantity"],
                          li["l_extendedprice"]):
        if pk in brand7 and qy < 0.2 * sums[pk] / cnts[pk]:
            tot += int(ep)
    return tot


def test_magic_set_seeds_decorrelated_aggregate(new_session):
    """The decorrelated AVG-per-key aggregate is seeded by a semi join
    against the filtered outer keys (magic set) and guarded by a STRICT
    Compact, and the result matches the host oracle."""
    s, part, li = _q17_session(new_session)
    plan, _outs, _est = s._plan_select(parse_sql(_Q17), None)
    semis = [n for n in _walk(plan)
             if isinstance(n, HashJoin) and n.how == "semi"]
    stricts = [n for n in _walk(plan)
               if isinstance(n, Compact) and n.strict]
    assert semis, "magic-set semi join missing from the q17 plan"
    assert stricts, "magic-set Compact is not strict"
    assert s.execute(_Q17).rows() == [(_q17_oracle(part, li),)]


def test_magic_set_plan_poison_parity(poison, new_session):
    s, _part, _li = _q17_session(new_session, n_part=500, n_li=3000)
    plan, _outs, _est = s._plan_select(parse_sql(_Q17), None)
    tables = {t: s.catalog.table_data(t)
              for t in referenced_tables(plan)
              if s.catalog.has_table(t)}
    prepare_index_probes(s.catalog, plan, tables)
    poison.assert_poison_invariant(
        lambda t: execute_plan(plan, t), tables)


# ---------------------------------------------------------------------------
# strict Compact: overflow surfaces instead of truncating
# ---------------------------------------------------------------------------


def test_strict_compact_overflow_raises_and_rescales(new_session):
    from oceanbase_tpu.exec.diag import CapacityOverflow
    from oceanbase_tpu.sql.optimizer import scale_capacities

    s = new_session()
    s.catalog.load_numpy("t", {"a": np.arange(1000, dtype=np.int64)})
    rel = s.catalog.table_data("t")
    plan = Compact(TableScan("t"), capacity=64, strict=True)
    with pytest.raises(CapacityOverflow):
        execute_plan(plan, {"t": rel})
    # the retry ladder scales the strict capacity out of the overflow
    scaled = scale_capacities(plan, 32)
    out = execute_plan(scaled, {"t": rel})
    assert int(np.asarray(out.mask_or_true()).sum()) == 1000
    # non-strict Compact with no cap never overflows
    out2 = execute_plan(Compact(TableScan("t")), {"t": rel})
    assert int(np.asarray(out2.mask_or_true()).sum()) == 1000


# ---------------------------------------------------------------------------
# gv$plan_choice ledger
# ---------------------------------------------------------------------------


def test_plan_choice_ledger_records_and_observes(db):
    s = db.session()
    s.execute("create table pa (id int primary key, k int, v int)")
    s.execute("create table pb (id int primary key, k int)")
    s.execute("insert into pa values "
              + ",".join(f"({i},{i % 20},{i})" for i in range(400)))
    s.execute("insert into pb values "
              + ",".join(f"({i},{i % 20})" for i in range(100)))
    s.execute("analyze table pa")
    s.execute("analyze table pb")
    s.execute("select count(*) from pa, pb where pa.k = pb.k")
    rows = db.plan_choice.rows()
    assert rows, "join bind did not reach the plan-choice ledger"
    rec = rows[-1]
    assert rec["enumerated"] >= 1 and rec["n_rels"] == 2
    assert rec["executions"] >= 1
    assert rec["pred_s"] > 0.0
    # the virtual table surfaces the same rows through SQL
    got = s.execute("select method, executions from gv$plan_choice")
    assert len(got.rows()) == len(rows)


# ---------------------------------------------------------------------------
# general partition-wise matching (choose_affinity)
# ---------------------------------------------------------------------------


def test_choose_affinity_claims_multiple_joins():
    """A bushy plan with two independent scan-to-scan joins co-shards
    BOTH (the old planner stopped after the first match)."""
    from oceanbase_tpu.px.planner import choose_affinity
    from oceanbase_tpu.vector import from_numpy

    n = 4000
    rng = np.random.default_rng(11)
    tabs = {}
    for name, key in (("fa", "ak"), ("fb", "bk"),
                      ("fc", "ck"), ("fd", "dk")):
        tabs[name] = from_numpy({
            key: rng.integers(0, 500, n).astype(np.int64),
            name + "_v": rng.integers(0, 9, n).astype(np.int64)})
    j1 = HashJoin(TableScan("fa"), TableScan("fb"),
                  [ir.col("ak")], [ir.col("bk")], how="inner",
                  out_capacity=1 << 16)
    j2 = HashJoin(TableScan("fc"), TableScan("fd"),
                  [ir.col("ck")], [ir.col("dk")], how="inner",
                  out_capacity=1 << 16)
    top = HashJoin(j1, j2, [ir.col("ak")], [ir.col("ck")],
                   how="inner", out_capacity=1 << 18)
    aff, elide = choose_affinity(top, tabs)
    assert set(aff) == {"fa", "fb", "fc", "fd"}
    assert len(elide) == 2
    assert id(j1) in elide and id(j2) in elide


# ---------------------------------------------------------------------------
# satellites: timeout overlay, memstore total, cancel pinning, lane kills
# ---------------------------------------------------------------------------


def test_set_global_timeout_reaches_statement_deadline(db):
    """SET GLOBAL writes the tenant config overlay; the session must
    read the overlay (not db.config) when no session variable is set."""
    s = db.session()
    assert s._stmt_timeout_s() == float(db.config["query_timeout_s"])
    s.execute("set global query_timeout_s = 120")
    assert s._stmt_timeout_s() == 120.0
    # a fresh session of the same tenant sees the overlay too
    s2 = db.session()
    assert s2._stmt_timeout_s() == 120.0
    # the session variable wins over the overlay
    s.execute("set query_timeout_s = 7")
    assert s._stmt_timeout_s() == 7.0
    # cluster default untouched
    assert int(db.config["query_timeout_s"]) != 120


def test_memstore_throttle_running_total_stays_consistent():
    """used_bytes() is O(1) now — it must track the per-table ledger
    exactly across writes, partial flushes, and table drops."""
    from oceanbase_tpu.server.admission import MemstoreThrottle
    from oceanbase_tpu.server.config import Config

    cfg = Config()
    cfg.set("enable_rate_limit", True)
    cfg.set("memstore_limit_bytes", 1 << 22)
    thr = MemstoreThrottle(cfg)

    def ledger_total():
        with thr._lock:
            return sum(e["bytes"] for e in thr._tables.values())

    for i in range(50):
        thr.admit_write("t1", {"a": i})
        thr.admit_write("t2", {"a": i, "b": "x" * 20})
    assert thr.used_bytes() == ledger_total() > 0
    thr.on_flush("t1", remaining_rows=10)
    assert thr.used_bytes() == ledger_total()
    thr.on_flush("t2", remaining_rows=0)
    assert thr.used_bytes() == ledger_total()
    thr.drop_table("t1")
    assert thr.used_bytes() == ledger_total()
    thr.drop_table("t2")
    assert thr.used_bytes() == ledger_total() == 0


def test_cancel_registry_pins_inflight_entries():
    """An Event pinned by an executing fragment must survive LRU
    pressure from >MAX_ENTRIES other tokens; unpinned tombstones stay
    bounded."""
    from oceanbase_tpu.px.dtl import CancelRegistry

    reg = CancelRegistry()
    ev = reg.pin("inflight")
    for i in range(CancelRegistry.MAX_ENTRIES + 50):
        reg.entry(f"t{i}")
    # identity check: entry() would re-create a fresh Event if the
    # pinned one had been evicted, silently orphaning the cancel
    assert reg.entry("inflight") is ev
    assert reg.cancel("inflight") is False  # first set: wasn't flagged
    assert ev.is_set()
    assert reg.cancel("inflight") is True  # idempotent re-apply
    reg.unpin("inflight")
    for i in range(CancelRegistry.MAX_ENTRIES + 50):
        reg.entry(f"u{i}")
    assert len(reg._entries) <= CancelRegistry.MAX_ENTRIES


def test_running_kill_and_timeout_bump_lane_counters():
    """KILL/timeout observed at a RUNNING checkpoint must count in the
    per-tenant gv$tenant_resource lane, not only the global counter."""
    from oceanbase_tpu.server.admission import (
        AdmissionController,
        QueryKilled,
        QueryTimeout,
        StmtCtx,
        activate,
        checkpoint,
    )
    from oceanbase_tpu.server.config import Config

    adm = AdmissionController(Config())
    ctx = StmtCtx(session_id=51, tenant="lt", controller=adm)
    adm.acquire(ctx)
    ctx.kill("test")
    with activate(ctx):
        with pytest.raises(QueryKilled):
            checkpoint()
    adm.release(ctx)
    rows = {r["tenant"]: r for r in adm.stats()}
    assert rows["lt"]["kills"] == 1

    tctx = StmtCtx(session_id=52, tenant="lt", controller=adm,
                   timeout_s=0.01)
    adm.acquire(tctx)
    time.sleep(0.03)
    with activate(tctx):
        with pytest.raises(QueryTimeout):
            checkpoint()
    adm.release(tctx)
    rows = {r["tenant"]: r for r in adm.stats()}
    assert rows["lt"]["timeouts"] == 1


# ---------------------------------------------------------------------------
# CREATE / DROP INDEX on a directly loaded table
# ---------------------------------------------------------------------------


def test_create_and_drop_index_on_a_loaded_table(new_session):
    s = new_session()
    s.catalog.load_numpy("t", {"a": np.arange(10, dtype=np.int64),
                               "k": np.arange(10, dtype=np.int64)})
    s.execute("create index ix on t (k)")
    td = s.catalog.table_def("t")
    assert any(i.name == "ix" for i in td.indexes)
    with pytest.raises(Exception):
        s.execute("create index ix on t (k)")  # duplicate name
    with pytest.raises(Exception):
        s.execute("create index ix2 on t (missing)")  # unknown column
    s.execute("drop index ix on t")
    assert not any(i.name == "ix"
                   for i in s.catalog.table_def("t").indexes)
    s.execute("drop index if exists ix on t")  # idempotent
