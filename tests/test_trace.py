"""Full-link query tracing (server/trace.py): span-tree shape for serial
and 3-node DTL queries, sampling knobs, slow-query retention, the
audit<->trace join, ASH/trace integration, and the poison-lane guarantee
that tracing never changes results."""

from __future__ import annotations

import json
import time

import numpy as np
import pytest

from oceanbase_tpu.server import Database
from test_multinode import Cluster

Q_AGG = ("select v, sum(k) as s from t where k < 90 "
         "group by v order by v")


@pytest.fixture()
def db(tmp_path):
    d = Database(str(tmp_path / "db"))
    s = d.session()
    s.execute("create table t (k int primary key, v int)")
    vals = ", ".join(f"({i}, {i % 7})" for i in range(100))
    s.execute(f"insert into t values {vals}")
    yield d, s
    d.close()


def _trace_rows(sess, trace_id):
    r = sess.execute(
        "select trace_id, span_id, parent_span_id, node, span_name,"
        " elapsed_s, tags from gv$trace")
    return [row for row in r.rows() if row[0] == trace_id]


def _audit_trace_id(sess, sql_prefix):
    r = sess.execute("select sql, trace_id from gv$sql_audit")
    hits = [t for q, t in r.rows() if q.startswith(sql_prefix)]
    assert hits, f"no audit row for {sql_prefix!r}"
    return hits[-1]


# ---------------------------------------------------------------------------
# serial span tree
# ---------------------------------------------------------------------------


def test_serial_span_tree_shape(db):
    _d, s = db
    s.execute(Q_AGG)
    tid = _audit_trace_id(s, "select v, sum(k)")
    assert tid, "statement did not keep a trace at sample_rate=1.0"
    spans = _trace_rows(s, tid)
    names = [r[4] for r in spans]
    assert "statement" in names and "compile" in names \
        and "execute" in names and "plan.execute" in names
    # exactly one root, and every parent edge lands on a known span
    ids = {r[1] for r in spans}
    roots = [r for r in spans if r[2] == 0]
    assert len(roots) == 1 and roots[0][4] == "statement"
    for row in spans:
        assert row[2] == 0 or row[2] in ids, f"orphan span {row}"
    # compile/execute are children of the statement root
    root_id = roots[0][1]
    by_name = {r[4]: r for r in spans}
    assert by_name["compile"][2] == root_id
    assert by_name["execute"][2] == root_id
    assert by_name["plan.execute"][2] == by_name["execute"][1]
    # every host phase of the statement is a child of the root ...
    for phase in ("parse", "admission", "virtuals", "plan.prepare",
                  "plan.record", "materialize"):
        assert by_name[phase][2] == root_id, phase
    assert by_name["tables"][2] == by_name["execute"][1]
    # ... and plan.execute's own phases ride under it (the zero-duration
    # op.<Name> rows are gone: gv$sql_plan_monitor has them)
    pe = by_name["plan.execute"][1]
    for phase in ("plan.dispatch", "plan.device_wait", "plan.monitor"):
        assert by_name[phase][2] == pe, phase
    assert not [n for n in names if n.startswith("op.")]
    assert json.loads(by_name["plan.execute"][6])["plan_hash"]
    # first execution of this fingerprint traced XLA, inside the dispatch
    assert by_name["xla.compile"][2] == by_name["plan.dispatch"][1]
    assert json.loads(by_name["xla.compile"][6])["bytes_accessed"] > 0
    # the first read of t built its device copy, inside `tables`
    assert by_name["storage.device_copy"][2] == by_name["tables"][1]
    assert json.loads(by_name["storage.device_copy"][6])["rows"] == 100


def test_show_trace_renders_last_statement(db):
    _d, s = db
    s.execute(Q_AGG)
    r = s.execute("show trace")
    assert r.rowcount > 0
    rows = r.rows()
    assert rows[0][0] == "statement"
    # children render indented under the root
    assert any(op.startswith("  ") for op, *_ in rows[1:])
    # SHOW TRACE must not clobber the trace it displays
    again = s.execute("show trace")
    assert [x[0] for x in again.rows()] == [x[0] for x in rows]


def test_audit_join_and_compile_s(db):
    _d, s = db
    s.execute(Q_AGG)
    r = s.execute(
        "select a.sql, t.span_name from gv$sql_audit a, gv$trace t"
        " where a.trace_id = t.trace_id and t.parent_span_id = 0")
    joined = [q for q, n in r.rows() if q.startswith("select v, sum")]
    assert joined, "audit row did not join its gv$trace tree"


# ---------------------------------------------------------------------------
# sampling / retention knobs
# ---------------------------------------------------------------------------


def test_sample_rate_zero_drops_fast_queries(db):
    d, s = db
    s.execute("alter system set trace_sample_rate = 0.0")
    s.execute("alter system set trace_slow_threshold_s = 100.0")
    try:
        dropped_before = d.trace_registry.traces_dropped
        s.execute("select k from t where k = 1")
        assert _audit_trace_id(s, "select k from t where k = 1") == ""
        assert d.trace_registry.traces_dropped > dropped_before
    finally:
        s.execute("alter system set trace_sample_rate = 1.0")
        s.execute("alter system set trace_slow_threshold_s = 1.0")


def test_show_trace_empty_when_sampled_away(db):
    _d, s = db
    s.execute(Q_AGG)  # kept at rate 1.0
    assert s.execute("show trace").rowcount > 0
    s.execute("alter system set trace_sample_rate = 0.0")
    s.execute("alter system set trace_slow_threshold_s = 100.0")
    try:
        s.execute("select k from t where k = 2")  # dropped
        # SHOW TRACE must NOT fall back to the older kept tree
        assert s.execute("show trace").rowcount == 0
    finally:
        s.execute("alter system set trace_sample_rate = 1.0")
        s.execute("alter system set trace_slow_threshold_s = 1.0")


def test_slow_query_always_traced(db):
    d, s = db
    s.execute("alter system set trace_sample_rate = 0.0")
    s.execute("alter system set trace_slow_threshold_s = 0.0")  # all "slow"
    try:
        s.execute("select count(*) from t")
        tid = _audit_trace_id(s, "select count(*) from t")
        assert tid and _trace_rows(s, tid), \
            "slow statement lost its trace to the sample draw"
    finally:
        s.execute("alter system set trace_sample_rate = 1.0")
        s.execute("alter system set trace_slow_threshold_s = 1.0")


def test_trace_disabled_is_silent(db):
    d, s = db
    s.execute("alter system set enable_query_trace = false")
    try:
        kept = d.trace_registry.traces_kept
        res = s.execute(Q_AGG)
        assert res.rowcount > 0
        assert d.trace_registry.traces_kept == kept
        assert _audit_trace_id(s, "select v, sum(k)") == ""
    finally:
        s.execute("alter system set enable_query_trace = true")


# ---------------------------------------------------------------------------
# ASH / system events
# ---------------------------------------------------------------------------


def test_ash_samples_carry_trace_id(db):
    d, s = db
    # the session's ASH slot carries the live trace_id during execution;
    # sample the registered slot directly (the sampler thread races a
    # short statement, so drive sample_once by hand)
    s._ash_state.update(active=True, sql="select 1", state="executing",
                        trace_id="cafebabe")
    d.ash.sample_once()
    s._ash_state.update(active=False, trace_id="")
    r = s.execute("select session_id, trace_id from"
                  " gv$active_session_history")
    assert (s.session_id, "cafebabe") in r.rows()


def test_ash_state_tracks_statement_trace(db):
    d, s = db
    seen = {}
    orig = s._materialize_virtuals

    def spy(stmt):
        seen["trace_id"] = s._ash_state.get("trace_id")
        return orig(stmt)

    s._materialize_virtuals = spy
    s.execute("select count(*) from t")
    assert seen["trace_id"], "no live trace_id in the ASH slot"
    assert s._ash_state["trace_id"] == ""  # cleared at statement end


def test_system_event_view(db):
    d, s = db
    d.wait_events.add("unit test wait", 0.25)
    r = s.execute("select event, total_waits, time_waited_s"
                  " from gv$system_event")
    rows = {e: (w, t) for e, w, t in r.rows()}
    assert rows["unit test wait"][0] == 1
    assert rows["unit test wait"][1] == pytest.approx(0.25)


def test_ring_recent_slices_tail():
    from oceanbase_tpu.server.monitor import AuditRecord, SqlAudit

    a = SqlAudit(capacity=100)
    for i in range(150):
        a.record(AuditRecord(sql=f"q{i}", session_id=i, tenant="sys",
                             start_ts=0.0, elapsed_s=0.0, rows=0))
    tail = a.recent(10)
    assert [r.sql for r in tail] == [f"q{i}" for i in range(140, 150)]
    assert len(a.recent(1000)) == 100


# ---------------------------------------------------------------------------
# tracing must never change results (poison-lane case)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["context", "no_context", "capture"])
def test_tracing_never_changes_results_poisoned(poison, mode, tmp_path):
    """The spans (records in a context; annotations + phase booking with
    none; events of a running profiler capture) never change a result."""
    import jax

    from oceanbase_tpu.catalog import Catalog
    from oceanbase_tpu.exec.plan import execute_plan, referenced_tables
    from oceanbase_tpu.server import trace as qtrace
    from oceanbase_tpu.sql.binder import Binder
    from oceanbase_tpu.sql.parser import parse_sql
    from oceanbase_tpu.vector import to_numpy

    cat = Catalog()
    rng = np.random.default_rng(3)
    n = 100
    cat.load_numpy("t", {
        "k": np.arange(n), "v": rng.integers(0, 9, n),
    }, primary_key=["k"])
    plan, _outs, _est = Binder(cat).bind_select(parse_sql(
        "select v, sum(k) as s, count(*) as c from t where k < 77"
        " group by v order by v"))
    tables = {t: cat.table_data(t).pad_to(256)
              for t in referenced_tables(plan)}
    poisoned = {t: poison.poison_pad_lanes(rel)
                for t, rel in tables.items()}
    clean = to_numpy(execute_plan(plan, tables))
    ctx = qtrace.TraceCtx("poisontest", node=0)
    if mode == "capture":
        jax.profiler.start_trace(str(tmp_path))
    try:
        with qtrace.activate(ctx if mode != "no_context" else None):
            traced = to_numpy(execute_plan(plan, poisoned))
    finally:
        if mode == "capture":
            jax.profiler.stop_trace()
    ok, why = poison.results_identical(clean, traced)
    assert ok, f"tracing + poisoned pad lanes changed results: {why}"
    if mode == "no_context":
        assert not ctx.spans
    else:
        assert {"plan.execute", "plan.dispatch", "plan.device_wait"} <= \
            {sp.name for sp in ctx.spans}


# ---------------------------------------------------------------------------
# 3-node cluster: remote halves of the tree
# ---------------------------------------------------------------------------


def test_dtl_remote_spans_parented(tmp_path):
    cl = Cluster(tmp_path, n=3)
    try:
        cl.execute(1, "create table t (k int primary key, v int)")
        vals = ", ".join(f"({i}, {i % 5})" for i in range(600))
        cl.execute(1, f"insert into t values {vals}")
        # wait for followers to apply so pushdown slices run remotely
        deadline = time.time() + 60
        while time.time() < deadline:
            counts = []
            for i in (2, 3):
                try:
                    r = cl.execute(i, "select count(*) from t",
                                   consistency="weak")
                    counts.append(int(r["arrays"][r["names"][0]][0]))
                except Exception:
                    counts.append(-1)
            if counts == [600, 600]:
                break
            time.sleep(0.3)
        cl.execute(1, "alter system set dtl_min_rows = 1")
        q = "select v, sum(k) as s from t where k < 500 group by v"
        res = cl.execute(1, q)
        assert res["node"] == 1

        audit = cl.execute(1, "select sql, trace_id from gv$sql_audit")
        tid = [t for s_, t in cl.rows(audit)
               if s_.startswith("select v, sum(k)") and t][-1]
        tr = cl.execute(
            1, "select trace_id, span_id, parent_span_id, node,"
            " span_name, tags from gv$trace")
        spans = [r for r in cl.rows(tr) if r[0] == tid]
        assert spans, "no gv$trace tree for the pushdown statement"
        ids = {r[1] for r in spans}
        by_id = {r[1]: r for r in spans}
        # remote halves present, and every remote span's parent chain
        # reaches the coordinator's tree (no orphans)
        remote = [r for r in spans if r[3] in (2, 3)]
        assert remote, "no remote spans shipped back with the replies"
        for r in remote:
            assert r[2] in ids, f"orphan remote span {r}"
        # the remote verb span hangs under the coordinator's rpc span
        rpc = {r[1]: r for r in spans if r[4] == "rpc.dtl.execute"}
        verb = [r for r in remote if r[4] == "dtl.execute"]
        assert verb and all(r[2] in rpc for r in verb)
        # and its peer tag names the node that executed it
        for r in verb:
            peer = json.loads(rpc[r[2]][5])["peer"]
            assert peer == r[3]
        # remote fragment execution appears under the verb span
        frags = [r for r in remote if r[4] == "dtl.fragment"]
        assert frags, "remote dtl.fragment span missing"
        # exchange structure on the coordinator
        names = {r[4] for r in spans if r[3] == 1}
        assert {"statement", "execute", "dtl.exchange", "dtl.slice",
                "dtl.merge"} <= names
    finally:
        cl.close()
