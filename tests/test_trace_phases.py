"""One clock for a statement (server/trace.py + exec/plan.py::ExecTimes):
every host phase owned in gv$sql_audit / gv$time_model (serial and PX),
the program's spans on the profiler's timeline as ``ob:<name>``, JAX's own
compile events and the collector's pauses booked to the statement that paid
them, operator scopes that change HLO metadata only, and the slow ring."""

from __future__ import annotations

import contextlib
import gc
import glob
import os
import time

import jax
import numpy as np
import pytest

from oceanbase_tpu.exec import plan as qplan
from oceanbase_tpu.server import Database
from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.server import trace as qtrace

N_ROWS = 200000
Q_GROUP = "select v, sum(k) as s, count(*) as c from big group by v order by v"
Q_JOIN = ("select b.v, sum(d.w) as s from big b, dim d where b.v = d.v "
          "group by b.v order by b.v")

#: gv$sql_audit columns that together must own a statement's wall
PHASE_COLUMNS = ("queue_s", "device_s", "xla_compile_s") + tuple(
    p for p in qtrace.PHASES if p != "compile_s")


@pytest.fixture(scope="module")
def db(tmp_path_factory):
    d = Database(str(tmp_path_factory.mktemp("phases") / "db"))
    s = d.session()
    rng = np.random.default_rng(11)
    s.catalog.load_numpy(
        "big", {"k": np.arange(N_ROWS), "v": rng.integers(0, 50, N_ROWS)},
        primary_key=["k"])
    s.catalog.load_numpy(
        "dim", {"v": np.arange(50), "w": np.arange(50) * 3},
        primary_key=["v"])
    yield d
    d.close()


def _audit(sess, prefix):
    r = sess.execute("select * from gv$sql_audit")
    i = r.names.index("sql")
    return [dict(zip(r.names, row)) for row in r.rows()
            if row[i].startswith(prefix)]


def _owned_share(row) -> float:
    return sum(row[c] for c in PHASE_COLUMNS) / row["elapsed_s"]


def test_serial_phases_sum_to_elapsed(db):
    s = db.session()
    # the load registered big's device copy (a first read builds nothing);
    # the miss a statement pays after an eviction or a reopen is what
    # ``device_copy_s`` owns
    s.catalog.invalidate("big")
    for _ in range(6):
        s.execute(Q_GROUP).rows()
    rows = _audit(s, "select v, sum(k) as s, count(*)")
    first, warm = rows[0], rows[1:]
    # the first execution compiled: the AOT bracket owns both windows
    assert first["lower_s"] > 0 and first["xla_compile_s"] > 0
    assert first["device_copy_s"] > 0  # and built big's device copy
    for row in warm:
        assert row["lower_s"] == 0 and row["xla_compile_s"] == 0
        assert row["device_s"] > 0 and row["dispatch_s"] > 0
        for col in ("parse_s", "bind_s", "prepare_s", "tables_s",
                    "monitor_s", "record_s", "materialize_s", "close_s"):
            assert row[col] > 0, col
        share = _owned_share(row)
        assert share <= 1.02, (share, row)
        assert row["other_s"] == pytest.approx(
            row["elapsed_s"] * (1.0 - share), abs=1e-6)
    # at least 90% of every warm statement is owned; under a loaded
    # machine one statement may lose its thread between two spans
    shares = sorted(_owned_share(row) for row in warm)
    assert shares[1] >= 0.90 and shares[0] >= 0.60, shares
    # the compile is owned too: nothing of it is left in dispatch_s
    assert _owned_share(first) >= 0.90
    assert first["dispatch_s"] < first["lower_s"] + first["xla_compile_s"]


def test_px_phases_sum_to_elapsed(db):
    s = db.session()
    s.execute("set px_dop = 4")
    try:
        for _ in range(4):
            s.execute(Q_JOIN).rows()
        assert [r[0].strip() for r in s.execute("show trace").rows()
                ].count("px.execute") == 1
    finally:
        s.execute("set px_dop = 1")
    rows = _audit(s, "select b.v, sum(d.w)")
    first, warm = rows[0], rows[1:]
    # the shard program's first execution lowered and compiled inside the
    # executable's bracket, as a serial plan's does
    for col in ("lower_s", "xla_compile_s"):
        assert first[col] > 0, col
    assert _owned_share(first) >= 0.90
    for row in warm:
        for col in ("device_s", "dispatch_s", "shard_s", "unshard_s",
                    "merge_s", "host_s"):
            assert row[col] > 0, col
        assert _owned_share(row) <= 1.02, row
    shares = sorted(_owned_share(row) for row in warm)
    assert shares[1] >= 0.90 and shares[0] >= 0.60, shares


def test_time_model_gains_the_phases(db):
    s = db.session()
    s.execute(Q_GROUP).rows()
    tm = {r[0]: r[1] for r in s.execute(
        "select phase, seconds from gv$time_model where tenant = 'sys'"
    ).rows()}
    for phase in ("parse_s", "tables_s", "materialize_s", "record_s",
                  "gc_s", "other_s", "close_s", "device_s", "elapsed_s"):
        assert phase in tm, phase
    assert tm["parse_s"] > 0 and tm["close_s"] > 0
    inside = sum(v for p, v in tm.items()
                 if p not in ("elapsed_s", "close_s", "other_s"))
    assert inside + tm["other_s"] == pytest.approx(tm["elapsed_s"], rel=1e-3)


@contextlib.contextmanager
def _capture(directory):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(directory), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _ob_events(directory):
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(str(directory), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    out = []
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name[3:], e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events if e.name.startswith("ob:")]
    return out


def test_every_span_is_on_the_profilers_timeline(db, tmp_path):
    s = db.session()
    s.execute(Q_GROUP).rows()
    s.execute("set px_dop = 4")
    s.execute(Q_JOIN).rows()
    with _capture(tmp_path):
        s.execute(Q_JOIN).rows()
        s.execute("set px_dop = 1")
        s.execute(Q_GROUP).rows()
    evs = _ob_events(tmp_path)
    roots = sorted((a, b) for n, a, b in evs if n == "statement")
    assert len(roots) == 3
    names = {n for n, _a, _b in evs}
    want = {"parse", "admission", "virtuals", "compile", "plan.prepare",
            "execute", "tables", "plan.execute", "plan.dispatch",
            "plan.device_wait", "plan.monitor", "plan.record",
            "materialize", "statement.close",
            "px.execute", "px.shard", "px.program", "px.unshard",
            "px.merge", "px.device_wait"}
    assert want <= names, want - names
    for n, a, b in evs:
        inside = any(r0 <= a and b <= r1 for r0, r1 in roots)
        if n in ("statement.close", "gc"):
            continue  # after the root closed / wherever it struck
        assert inside or n == "statement", f"ob:{n} outside ob:statement"
    # statement.close follows its root at once
    closes = sorted(a for n, a, _b in evs if n == "statement.close")
    assert len(closes) == 3
    assert all(c >= r1 for c, (_r0, r1) in zip(closes, roots))


def test_span_without_a_statement_is_the_annotation_alone(tmp_path):
    assert qtrace.current() is None
    qplan.reset_exec_times()
    with _capture(tmp_path):
        with qtrace.span("scrub.round") as sp:
            time.sleep(0.002)
        with qtrace.span("parse"):
            pass
    assert sp.elapsed_s >= 0.002 and sp.self_s == sp.elapsed_s
    assert {n for n, _a, _b in _ob_events(tmp_path)} == \
        {"scrub.round", "parse"}
    # a phase span books even with no trace context
    assert qplan.exec_times().parse_s > 0


def test_self_time_gives_up_children_and_charges():
    acc = qplan.reset_exec_times()
    with qtrace.span("tables") as outer:
        time.sleep(0.003)
        with qtrace.span("storage.device_copy") as inner:
            time.sleep(0.004)
        qtrace.add_span("admission.wait", 0.001)
    assert inner.self_s >= 0.004
    assert outer.elapsed_s >= 0.007
    assert outer.self_s == pytest.approx(
        outer.elapsed_s - inner.elapsed_s - 0.001, abs=1e-6)
    assert acc.tables_s == pytest.approx(outer.self_s)
    assert acc.device_copy_s == pytest.approx(inner.self_s)


def test_jax_compile_events_book_self_time_once():
    """Nested events arrive inner first; an event gives up what the events
    it encloses already own; a bracketed compile books nothing."""
    acc = qplan.reset_exec_times()
    n0 = qmetrics.counter_value("jax.compile_events")
    t0 = qmetrics.counter_value("jax.compile_ns", stage="trace")
    trace = "/jax/core/compile/jaxpr_trace_duration"
    backend = "/jax/core/compile/backend_compile_duration"
    lookup = "/jax/compilation_cache/cache_retrieval_time_sec"
    def span_event(name, secs):
        now = time.time()
        qtrace._on_jax_span(name, now - secs, now)

    with qtrace.span("px.program") as sp:
        time.sleep(0.012)
        span_event(trace, 0.004)      # inner pjit trace
        span_event(trace, 0.010)      # the trace around it
        time.sleep(0.006)
        qtrace._on_jax_duration(lookup, 0.003)
        span_event(backend, 0.005)    # holds the lookup
        span_event("/jax/other", 9.0)
        qtrace._on_jax_duration("/jax/other_sec", 9.0)
        with qtrace.bracketed_compile():
            span_event(trace, 0.001)
    assert acc.trace_s == pytest.approx(0.010, abs=2e-4)
    assert acc.cache_lookup_s == pytest.approx(0.003, abs=2e-4)
    assert acc.compile_s == pytest.approx(0.002, abs=2e-4)
    assert sp.self_s == pytest.approx(sp.elapsed_s - 0.015, abs=5e-4)
    assert acc.dispatch_s == pytest.approx(sp.self_s)
    assert qmetrics.counter_value("jax.compile_events") == n0 + 5
    assert qmetrics.counter_value("jax.compile_ns", stage="trace") - t0 \
        == pytest.approx(0.011e9, abs=3e5)


def test_a_trace_of_a_thousand_nested_traces_is_owned_once():
    """One program's trace holds hundreds of inner pjit traces (PX Q3:
    945 events): all of them are still there to be given up when the
    event around them ends."""
    qtrace.begin_statement()
    acc = qplan.reset_exec_times()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    # the events carry made-up stamps: a real collection among them would
    # book its own interval on the real clock and swallow some of them
    gc.disable()
    try:
        t0 = time.time()
        for k in range(1000):
            qtrace._on_jax_span(trace, t0 + k * 1e-4, t0 + k * 1e-4 + 5e-5)
        qtrace._on_jax_span(trace, t0 - 0.01, t0 + 0.11)
    finally:
        gc.enable()
    assert acc.trace_s == pytest.approx(0.12, abs=1e-6)


def test_forced_collection_lands_in_the_statements_gc_s(db, monkeypatch):
    s = db.session()
    s.execute(Q_GROUP).rows()
    real = type(s)._materialize

    def collecting(self, rel, outputs, tags):
        gc.collect()
        return real(self, rel, outputs, tags)

    monkeypatch.setattr(type(s), "_materialize", collecting)
    s.execute(Q_GROUP).rows()
    monkeypatch.undo()
    row = _audit(s, "select v, sum(k) as s, count(*)")[-1]
    assert row["gc_s"] > 0
    assert 0.75 <= _owned_share(row) <= 1.02  # the pause is owned once
    # the counters follow at the next statement's start
    assert qmetrics.counter_value("runtime.gc_pause_ns") >= \
        int(row["gc_s"] * 1e9) - 1000
    assert qmetrics.counter_value("runtime.gc_collections", gen=2) >= 1


def test_scopes_change_hlo_metadata_only(db, monkeypatch):
    """fingerprint(), plan_hash and the AOT signature do not see the
    operator scopes; the lowered module differs in its location metadata
    alone (which the persistent cache leaves out of its key); and the
    plan compiles once."""
    from oceanbase_tpu.exec.plan import (
        Program, _input_signature, _lower, executable_for,
        referenced_tables)
    from oceanbase_tpu.sql.binder import Binder
    from oceanbase_tpu.sql.parser import parse_sql

    s = db.session()
    plan, _outs, _est = Binder(s.catalog).bind_select(parse_sql(Q_JOIN))
    tables = {t: s.catalog.table_data(t) for t in referenced_tables(plan)}
    key = plan.fingerprint()
    sig = _input_signature(tables)

    def lowered_text(debug):
        executable_for.cache_clear()
        bundle = executable_for(Program(_lower, (plan,), key, key))
        low = bundle._run.lower(tables)
        return bundle.stats.plan_hash, low.as_text(debug_info=debug)

    hash_scoped, scoped = lowered_text(False)
    _h, scoped_debug = lowered_text(True)
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    hash_plain, plain = lowered_text(False)
    _h, plain_debug = lowered_text(True)
    monkeypatch.undo()
    executable_for.cache_clear()
    assert plan.fingerprint() == key and _input_signature(tables) == sig
    assert hash_scoped == hash_plain
    assert scoped == plain
    for scope in ("HashJoin#", "join.probe", "join.sort_build", "GroupBy#",
                  "groupby.segment_reduce", "Sort#"):
        assert scope in scoped_debug, scope
        assert scope not in plain_debug, scope
    # nothing compiles twice: three executions, one XLA trace
    def traces():
        # plans over gv$ tables are the reader's own
        return {h: n for h, n, text in s.execute(
            "select plan_hash, xla_trace_count, plan_text "
            "from gv$plan_cache").rows() if "gv$" not in text}

    before = traces()
    for _ in range(3):
        s.execute(Q_JOIN).rows()
    after = traces()
    assert sum(after.values()) - sum(before.values()) == 1


def test_slow_trees_outlive_the_span_ring():
    reg = qtrace.TraceRegistry(max_spans=40)

    def tree(tid, n=8):
        return [qtrace.Span(tid, i + 1, 0 if i == 0 else 1, 0,
                            "statement" if i == 0 else "parse",
                            time.time(), 0.001) for i in range(n)]

    reg.add(tree("slow-1"), slow=True)
    for k in range(50):
        reg.add(tree(f"fast-{k}"))
    assert len(reg.trace("slow-1")) == 8          # not evicted
    assert reg.trace("fast-0") == []              # the ring turned over
    assert len(reg.trace("fast-49")) == 8
    held = {sp.trace_id for sp in reg.recent()}
    assert "slow-1" in held and "fast-49" in held
    for k in range(reg.SLOW_TREES + 3):           # the slow ring is bounded
        reg.add(tree(f"slow-x{k}"), slow=True)
    assert reg.trace("slow-1") == []
    assert reg.traces_kept == 1 + 50 + reg.SLOW_TREES + 3


def test_statement_far_over_its_baseline_goes_to_the_slow_ring(
        db, monkeypatch):
    s = db.session()
    sql = "select count(*) as c from big where v < 7"
    for _ in range(db.plan_history.WARMUP + 2):
        s.execute(sql).rows()
    before = set(db.trace_registry.slow_trace_ids())
    from oceanbase_tpu.sql import session as sessmod

    real = sessmod.execute_plan

    def stalled(*a, **kw):
        time.sleep(0.25)
        return real(*a, **kw)

    monkeypatch.setattr(sessmod, "execute_plan", stalled)
    s.execute(sql).rows()
    monkeypatch.undo()
    slow = [t for t in db.trace_registry.slow_trace_ids()
            if t not in before]
    assert slow == [_audit(s, "select count(*) as c from big")[-1]["trace_id"]]
    # gv$trace serves it like any other tree
    got = s.execute("select span_name from gv$trace where trace_id = "
                    f"'{slow[0]}'").rows()
    assert ("statement",) in got and ("plan.record",) in got


def test_warm_statement_leaves_the_collector_nothing(db):
    """The young generations' pauses are the tail of a short statement:
    a warm statement makes no reference cycle (the collector finds
    nothing unreachable after a run of them) and keeps a handful of
    container objects alive (the packed tree and its few tag dicts, the
    audit row and its accumulator), not one per span and closure."""
    s = db.session()
    sql = "select count(*) as c from big where v < 9"
    for _ in range(40):
        s.execute(sql).rows()
    runs = 50
    gc.collect()
    gc.disable()
    try:
        start = gc.get_count()[0]
        for _ in range(runs):
            s.execute(sql).rows()
        kept = (gc.get_count()[0] - start) / runs
        unreachable = gc.collect()
    finally:
        gc.enable()
    assert unreachable == 0
    assert kept <= 16, kept
    # the packed tree reads back as the tree the statement collected
    tid = _audit(s, "select count(*) as c from big where v < 9")[-1][
        "trace_id"]
    spans = db.trace_registry.trace(tid)
    by_id = {sp.span_id: sp for sp in spans}
    assert len(by_id) == len(spans) >= 12
    root = [sp for sp in spans if sp.parent_id not in by_id]
    assert [sp.name for sp in root] == ["statement"]
    assert root[0].tags["sql"].startswith("select count(*)")
    assert spans == db.trace_registry.trace(tid)  # ids drawn once


def test_rpc_reply_keeps_plan_phases_on_their_node():
    ctx = qtrace.TraceCtx("wiretest", node=2)
    with qtrace.activate(ctx):
        with qtrace.span("dtl.fragment"):
            with qtrace.span("plan.execute"):
                with qtrace.span("plan.dispatch"):
                    with qtrace.span("xla.compile"):
                        pass
                with qtrace.span("plan.device_wait"):
                    pass
    wire = {d["nm"]: d for d in ctx.wire_spans()}
    assert set(wire) == {"dtl.fragment", "plan.execute", "xla.compile"}
    # what hung under a phase that stays behind moves up to its parent
    assert wire["xla.compile"]["p"] == wire["plan.execute"]["s"]
    assert wire["plan.execute"]["p"] == wire["dtl.fragment"]["s"]
    assert len(ctx.snapshot()) == 5  # the node's own gv$trace has them all
