"""One executable cache and one note lane for serial and PX plans
(exec/plan.py::_PlanExecutable, exec/diag.py::note): a PX statement's
shard program is a cached executable like any other (a ``gv$plan_cache``
row, the AOT bracket's ``lower_s`` / ``xla_compile_s``, a retrace at a new
capacity bucket marked as a compile), its notes are kept per signature,
and every row of ``diag.NOTE_SERIES`` books on every execution."""

from __future__ import annotations

import json

import pytest

from oceanbase_tpu.exec import diag
from oceanbase_tpu.exec import plan as qplan
from oceanbase_tpu.px import planner as px_planner
from oceanbase_tpu.server import Database
from oceanbase_tpu.server import metrics as qmetrics

Q_GROUP = "select g, sum(v) as s, count(*) as c from a group by g order by g"
Q_JOIN = "select count(*) as c, sum(w) as s from a join b on a.k = b.k"


@pytest.fixture()
def pair(tmp_path):
    """Two tables hash-partitioned over four devices, ten rows a
    partition: one bucket (64 lanes) under the ladder's next."""
    db = Database(str(tmp_path))
    s = db.session()
    s.execute("create table a (k bigint, g bigint, v decimal(15,2), "
              "primary key (k)) partition by hash (k) partitions 4")
    s.execute("create table b (k bigint, w bigint, primary key (k)) "
              "partition by hash (k) partitions 4")
    s.execute("insert into a values " + ", ".join(
        f"({k}, {k % 3}, {k}.25)" for k in range(40)))
    s.execute("insert into b values " + ", ".join(
        f"({k}, {k * 2})" for k in range(40)))
    qplan.executable_for.cache_clear()
    # gv$plan_cache's rows are the process's: a file that ran before on
    # this worker must not lend its PX rows to the counts below
    qplan.reset_plan_cache_stats()
    yield s
    db.close()
    qplan.executable_for.cache_clear()


def _px(s, sql):
    """At ``px_dop`` 4, which stays set: ``show trace`` is of the last
    statement."""
    s.execute("set px_dop = 4")
    rows = s.execute(sql).rows()
    assert s._last_px
    return rows


def _grow_a(s):
    """Every partition of ``a`` over 64 rows: the next capacity bucket."""
    s.execute("insert into a values " + ", ".join(
        f"({k}, {k % 3}, {k}.25)" for k in range(40, 400)))


def _px_rows(s) -> dict:
    r = s.execute("select * from gv$plan_cache")
    rows = [dict(zip(r.names, row)) for row in r.rows()]
    return {row["plan_hash"]: row for row in rows
            if row["plan_text"].startswith("px(dop=4")}


def _audit(s, prefix):
    r = s.execute("select * from gv$sql_audit")
    return [row for row in (dict(zip(r.names, x)) for x in r.rows())
            if row["sql"].startswith(prefix)]


def _span_tags(s, name) -> dict:
    for row in s.execute("show trace").rows():
        if row[0].strip() == name:
            return json.loads(row[4] or "{}")
    raise AssertionError(f"no span {name}")


def test_px_program_has_a_plan_cache_row_and_the_brackets_seconds(pair):
    before = _px_rows(pair)
    first = _px(pair, Q_GROUP)
    assert _span_tags(pair, "px.program").get("compiled") == 1
    # the compile is a child of px.program, as of plan.dispatch
    names = [r[0] for r in pair.execute("show trace").rows()]
    at = [n.strip() for n in names].index("px.program")
    assert names[at + 1].strip() == "xla.compile"
    assert len(names[at + 1]) - len(names[at + 1].lstrip()) > \
        len(names[at]) - len(names[at].lstrip())
    assert _px(pair, Q_GROUP) == first
    assert "compiled" not in _span_tags(pair, "px.program")
    new = [row for h, row in _px_rows(pair).items() if h not in before]
    assert len(new) == 1, new
    row = new[0]
    assert row["executions"] == 2 and row["xla_trace_count"] == 1
    assert row["hit_count"] == 1
    assert row["last_compile_s"] > 0
    assert row["flops"] > 0 and row["bytes_accessed"] > 0
    assert row["peak_memory"] > 0
    cold, warm = _audit(pair, "select g, sum(v)")[-2:]
    assert cold["lower_s"] > 0 and cold["xla_compile_s"] > 0
    assert warm["lower_s"] == 0 and warm["xla_compile_s"] == 0


def test_px_retrace_at_a_new_bucket_is_a_compile(pair):
    db = pair.db

    def samples():
        return sum(r["executions"] for r in db.plan_history.rows())

    _px(pair, Q_GROUP)
    n0 = samples()
    _px(pair, Q_GROUP)
    assert samples() == n0 + 1          # a warm execution is a sample
    _grow_a(pair)
    compiles = qmetrics.counter_value("plan.compiles")
    n0 = samples()
    rows = _px(pair, Q_GROUP)
    assert sum(r[2] for r in rows) == 400
    assert qmetrics.counter_value("plan.compiles") == compiles + 1
    assert _span_tags(pair, "px.program").get("compiled") == 1
    assert samples() == n0              # compile-inflated: left out
    cold = _audit(pair, "select g, sum(v)")[-1]
    assert cold["lower_s"] > 0 and cold["xla_compile_s"] > 0
    # one executable, two signatures, one gv$plan_cache row
    (row,) = [r for r in _px_rows(pair).values() if r["executions"] == 3]
    assert row["xla_trace_count"] == 2


def test_two_signatures_of_a_px_plan_keep_their_own_notes(pair,
                                                          monkeypatch):
    from oceanbase_tpu.exec import ops

    calls = []
    call = qplan._PlanExecutable.call

    def spy(self, tables):
        got = call(self, tables)
        if self.program.shard is not None:
            calls.append((self, tables, got[-1]))
        return got

    monkeypatch.setattr(qplan._PlanExecutable, "call", spy)
    # 64 lanes a shard probe by search, 128 by merge: the shape rule's
    # floor between the two (lanes x bits of the build side; the join
    # builds on a primary key, and a join on its probe's lanes merges
    # from half the floor)
    monkeypatch.setattr(ops, "_MERGE_PROBE_MIN_GATHERS", 2 * 64 * 7)
    small = _px(pair, Q_JOIN)
    _grow_a(pair)
    pair.execute("insert into b values " + ", ".join(
        f"({k}, {k * 2})" for k in range(40, 400)))
    assert _px(pair, Q_JOIN) != small
    (exe, old_tables, old_notes), (exe2, _t, new_notes) = calls
    assert exe is exe2 and len(exe._execs) == 2
    assert old_notes["probe", "search"] == 1 and \
        ("probe", "merge") not in old_notes
    assert new_notes["probe", "merge"] == 1 and \
        ("probe", "search") not in new_notes
    # an execution at the older signature books the older trace's notes
    before = {k: qmetrics.counter_value("plan.join_probes", kind=k)
              for k in ("merge", "search")}
    noted = call(exe, old_tables)[-1]
    assert noted == old_notes and noted is not new_notes
    diag.book_notes(noted)
    assert qmetrics.counter_value("plan.join_probes", kind="search") == \
        before["search"] + 1
    assert qmetrics.counter_value("plan.join_probes", kind="merge") == \
        before["merge"]


@pytest.mark.parametrize("path", ["serial", "px"])
@pytest.mark.parametrize("what", sorted(diag.NOTE_SERIES))
def test_a_note_made_while_tracing_is_booked_on_every_execution(
        what, path, pair, monkeypatch):
    """Every row of the table, on both paths: the lowering notes
    (what, "by_test", 3) once a table scan, the executable keeps the
    count, each execution adds it to the row's series."""
    series, label = diag.NOTE_SERIES[what]
    labels = {label: "by_test"} if label else {}    # a series may have none

    def noting(lower):
        def wrapped(node, *rest):
            if isinstance(node, qplan.TableScan):
                diag.note(what, "by_test", 3)
            return lower(node, *rest)
        return wrapped

    monkeypatch.setattr(qplan, "_lower_inner", noting(qplan._lower_inner))
    monkeypatch.setattr(px_planner, "_dlower", noting(px_planner._dlower))
    pair.execute(f"set px_dop = {4 if path == 'px' else 1}")
    want = None
    for _ in range(3):
        before = qmetrics.counter_value(series, **labels)
        rows = pair.execute(Q_JOIN).rows()
        assert bool(pair._last_px) == (path == "px")
        after = qmetrics.counter_value(series, **labels)
        assert after - before == 6, (series, before, after)  # two scans
        want = want or rows
        assert rows == want


def test_a_note_without_a_series_raises_where_it_is_made():
    with pytest.raises(KeyError, match="nope"):
        diag.note("nope", "x")              # collector or not
    with diag.note_collect() as notes:
        with pytest.raises(KeyError, match="nope"):
            diag.note("nope", "x")
        diag.note("probe", "merge")
    assert notes == [("probe", "merge", 1)]
