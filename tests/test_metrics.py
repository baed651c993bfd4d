"""Metrics-plane suite (server/metrics.py + its surfaces).

Covers the ISSUE-7 acceptance checklist at tier-1 speed:

- registry semantics under threads (lock-free shards must not lose
  increments; dead-thread shards fold instead of leaking);
- log-bucket histogram percentile math against numpy percentiles
  (error bounded by the bucket growth factor), exact min/max;
- cross-node scrape merge parity (wire round-trip + merge_wire sums);
- gv$plan_cache cost columns populated after one compile
  (XLA cost_analysis / memory_analysis attribution);
- gv$memory pad-waste ratio reacting to ``shape_bucket_growth``;
- SHOW METRICS / gv$sysstat / gv$sysstat_histogram SQL faces;
- the obcheck ``metric.*`` family (seeded violations + clean tree);
- WaitEvents' histogram upgrade staying wire-compatible.
"""

import threading

import numpy as np
import pytest

from oceanbase_tpu.server import metrics as qmetrics


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Each test sees an empty registry and an enabled plane."""
    qmetrics.reset()
    qmetrics.set_enabled(True)
    yield
    qmetrics.reset()
    qmetrics.set_enabled(True)


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_undeclared_name_raises():
    with pytest.raises(KeyError):
        qmetrics.inc("test.never_declared_xyz")
    qmetrics.declare("test.h1", "histogram", "t")
    with pytest.raises(TypeError):
        qmetrics.inc("test.h1")  # wrong kind


def test_declare_idempotent_but_kind_stable():
    qmetrics.declare("test.c1", "counter", "t")
    qmetrics.declare("test.c1", "counter", "t")  # fine
    with pytest.raises(ValueError):
        qmetrics.declare("test.c1", "gauge", "t")


def test_counters_under_threads_lose_nothing():
    qmetrics.declare("test.thr", "counter", "t")
    n_threads, per = 8, 5000

    def worker(i):
        for _ in range(per):
            qmetrics.inc("test.thr", worker=i % 2)

    ts = [threading.Thread(target=worker, args=(i,))
          for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    # dead threads: their shards must FOLD into the retired pool, and
    # the total must be exact (each shard is single-writer)
    assert qmetrics.counter_value("test.thr") == n_threads * per
    assert qmetrics.counter_value("test.thr", worker=0) == \
        (n_threads // 2) * per


def test_disabled_plane_is_a_noop():
    qmetrics.declare("test.off", "counter", "t")
    qmetrics.set_enabled(False)
    qmetrics.inc("test.off", 100)
    qmetrics.set_enabled(True)
    assert qmetrics.counter_value("test.off") == 0


def test_gauge_last_write_wins():
    qmetrics.declare("test.g", "gauge", "t")
    qmetrics.set_gauge("test.g", 1.5)
    qmetrics.set_gauge("test.g", 2.5)
    snap = qmetrics.snapshot()
    assert snap["gauges"][("test.g", ())] == 2.5


# ---------------------------------------------------------------------------
# histogram math
# ---------------------------------------------------------------------------


def test_histogram_percentiles_vs_numpy():
    rng = np.random.default_rng(7)
    vals = rng.lognormal(mean=-6.0, sigma=1.5, size=20000)
    h = qmetrics.Histogram()
    for v in vals:
        h.observe(float(v))
    assert h.count == len(vals)
    assert h.min == pytest.approx(vals.min())
    assert h.max == pytest.approx(vals.max())
    assert h.sum == pytest.approx(vals.sum(), rel=1e-9)
    # a log-bucket estimate is off by at most one bucket width:
    # relative error bounded by the growth factor (plus interpolation
    # slack on the tail bucket)
    tol = qmetrics.HIST_GROWTH - 1.0 + 0.05
    for q in (50.0, 95.0, 99.0):
        want = float(np.percentile(vals, q))
        got = h.percentile(q)
        assert abs(got - want) <= tol * want, (q, got, want)


def test_histogram_bucket_bounds_are_consistent():
    for v in (1e-7, 1e-6, 2e-6, 1e-3, 0.5, 1.0, 37.0, 1e9):
        i = qmetrics.bucket_index(v)
        assert v <= qmetrics.bucket_bound(i)
        if i > 0:
            assert v > qmetrics.bucket_bound(i - 1)


def test_histogram_wire_roundtrip_and_merge():
    a, b = qmetrics.Histogram(), qmetrics.Histogram()
    for v in (0.001, 0.002, 0.1):
        a.observe(v)
    for v in (0.5, 0.004):
        b.observe(v)
    back = qmetrics.Histogram.from_wire(a.to_wire())
    assert back.count == a.count and back.sum == a.sum
    assert back.buckets == a.buckets
    m = a.copy()
    m.merge(b)
    assert m.count == 5
    assert m.min == 0.001 and m.max == 0.5
    assert sum(m.buckets.values()) == 5


# ---------------------------------------------------------------------------
# scrape wire + cross-node merge parity
# ---------------------------------------------------------------------------


def test_scrape_merge_parity():
    """Merging two nodes' wire bodies must equal the per-series sums —
    the property the cluster gv$sysstat aggregation rides on."""
    qmetrics.declare("test.m", "counter", "t")
    qmetrics.declare("test.ms", "histogram", "t")
    qmetrics.inc("test.m", 3, verb="a")
    qmetrics.observe("test.ms", 0.01)
    wire_a = qmetrics.wire_snapshot()
    qmetrics.reset()
    qmetrics.inc("test.m", 4, verb="a")
    qmetrics.inc("test.m", 5, verb="b")
    qmetrics.observe("test.ms", 0.02)
    qmetrics.observe("test.ms", 0.04)
    wire_b = qmetrics.wire_snapshot()

    merged = qmetrics.merge_wire(wire_a, wire_b)
    flat = qmetrics.wire_to_flat(merged)
    assert flat["test.m{verb=a}"] == 7
    assert flat["test.m{verb=b}"] == 5
    hists = {qmetrics.series_id(n, lbl): hw
             for n, lbl, hw in merged["hists"]}
    h = qmetrics.Histogram.from_wire(hists["test.ms"])
    assert h.count == 3
    assert h.sum == pytest.approx(0.07)
    assert h.min == pytest.approx(0.01) and h.max == pytest.approx(0.04)
    # merge is associative with the empty body (scrape of a fresh node)
    again = qmetrics.merge_wire(merged, {})
    assert qmetrics.wire_to_flat(again) == flat


def test_prom_text_exposition_shape():
    qmetrics.declare("test.p", "counter", "t")
    qmetrics.declare("test.ps", "histogram", "t")
    qmetrics.inc("test.p", 2, verb="x")
    qmetrics.observe("test.ps", 0.003)
    # land one observation in the overflow bucket: the exposition must
    # still emit exactly ONE +Inf line per series (a duplicate sample
    # makes the whole scrape unparseable to Prometheus)
    qmetrics.observe("test.ps", 1e12)
    text = qmetrics.prom_text()
    assert '# TYPE ob_test_p counter' in text
    assert 'ob_test_p{verb="x"} 2' in text
    assert '# TYPE ob_test_ps histogram' in text
    assert 'ob_test_ps_count 2' in text
    # cumulative buckets end at +Inf with the total count, exactly once
    assert text.count('ob_test_ps_bucket{le="+Inf"}') == 1
    assert 'ob_test_ps_bucket{le="+Inf"} 2' in text


# ---------------------------------------------------------------------------
# WaitEvents histogram upgrade (gv$system_event columns)
# ---------------------------------------------------------------------------


def test_wait_events_wire_compatible_and_extended():
    from oceanbase_tpu.server.monitor import WaitEvents

    we = WaitEvents()
    for s in (0.001, 0.002, 0.004, 0.100):
        we.add("dtl exchange", s)
    legacy = we.snapshot()
    assert legacy["dtl exchange"][0] == 4
    assert legacy["dtl exchange"][1] == pytest.approx(0.107)
    st = we.stats()["dtl exchange"]
    assert st["min"] == pytest.approx(0.001)
    assert st["max"] == pytest.approx(0.100)
    assert st["count"] == 4
    assert 0.001 <= st["p50"] <= 0.004 < st["p99"] <= 0.100


# ---------------------------------------------------------------------------
# SQL surfaces: gv$plan_cache cost columns, gv$memory, gv$sysstat
# ---------------------------------------------------------------------------


@pytest.fixture
def db(tmp_path):
    from oceanbase_tpu.server import Database

    d = Database(str(tmp_path / "db"))
    yield d
    d.close()


def test_plan_cache_cost_columns_after_one_compile(db):
    s = db.session()
    s.execute("create table t (a int primary key, b int)")
    s.execute("insert into t values " +
              ", ".join(f"({i}, {i * 3})" for i in range(200)))
    s.execute("select sum(b) from t where a < 100")
    r = s.execute(
        "select executions, xla_trace_count, flops, bytes_accessed,"
        " peak_memory, last_compile_s from gv$plan_cache"
        " where executions > 0 order by executions desc")
    rows = r.rows()
    assert rows, "no plan-cache entries after a query"
    # at least one executed plan carries nonzero XLA attribution
    attributed = [row for row in rows
                  if row[2] > 0 and row[3] > 0 and row[4] > 0]
    assert attributed, f"no cost attribution in {rows[:5]}"
    ex, traces, _f, _b, _m, compile_s = attributed[0]
    assert ex >= 1 and traces >= 1 and compile_s > 0


def test_plan_metrics_counters_flow(db):
    s = db.session()
    s.execute("create table t (a int primary key)")
    s.execute("insert into t values (1), (2), (3)")
    s.execute("select count(*) from t")
    assert qmetrics.counter_value("plan.compiles") >= 1
    assert qmetrics.counter_value("plan.executions") >= 1
    assert qmetrics.counter_value("sql.statements", tenant="sys") >= 3


def test_pad_waste_ratio_reacts_to_bucket_growth(db):
    s = db.session()
    s.execute("create table t (a int primary key, b int)")
    s.execute("insert into t values " +
              ", ".join(f"({i}, {i})" for i in range(100)))
    r = s.execute("select live_rows, buffer_capacity, pad_waste_ratio,"
                  " buffer_bytes, live_bytes from gv$memory"
                  " where table_name = 't'").rows()
    assert len(r) == 1
    live, cap, waste, buf_b, live_b = r[0]
    assert live == 100
    assert cap == 128  # floor 64, growth 2.0 ladder
    assert waste == pytest.approx(1.0 - 100 / 128)
    assert buf_b > live_b > 0

    s.execute("alter system set shape_bucket_growth = 4.0")
    r2 = s.execute("select buffer_capacity, pad_waste_ratio from"
                   " gv$memory where table_name = 't'").rows()
    cap2, waste2 = r2[0]
    assert cap2 == 256  # 64 * 4
    assert waste2 == pytest.approx(1.0 - 100 / 256)
    assert waste2 != waste


def test_sysstat_sql_face_and_show_metrics(db):
    s = db.session()
    s.execute("create table t (a int primary key)")
    s.execute("insert into t values (1)")
    s.execute("select * from t")
    rows = s.execute(
        "select stat_name, value from gv$sysstat"
        " where name = 'sql.statements'").rows()
    assert rows and all(v >= 1 for _n, v in rows)
    hrows = s.execute(
        "select stat_name, count, p50_s, p95_s, p99_s, max_s from"
        " gv$sysstat_histogram where name = 'sql.statement_s'").rows()
    assert hrows
    _n, cnt, p50, p95, p99, mx = hrows[0]
    assert cnt >= 3 and 0 < p50 <= p95 <= p99 <= mx
    lines = s.execute("show metrics").rows()
    text = "\n".join(r[0] for r in lines)
    assert "# TYPE ob_sql_statements counter" in text
    assert "ob_sql_statement_s_bucket" in text


def test_enable_metrics_knob(db):
    s = db.session()
    s.execute("create table t (a int primary key)")
    s.execute("alter system set enable_metrics = false")
    base = qmetrics.counter_value("sql.statements")
    s.execute("insert into t values (1)")
    assert qmetrics.counter_value("sql.statements") == base
    # the re-enabling ALTER counts itself: the knob flips mid-statement,
    # before the statement boundary where sql.statements increments
    s.execute("alter system set enable_metrics = true")
    s.execute("insert into t values (2)")
    assert qmetrics.counter_value("sql.statements") == base + 2


# ---------------------------------------------------------------------------
# obcheck metric.* family
# ---------------------------------------------------------------------------

METRIC_BAD = '''
import jax
from oceanbase_tpu.server import metrics as qmetrics

qmetrics.declare("good.counter", "counter", "d")
GOOD = qmetrics.declare("good.const", "counter", "d")

def traced(x):
    qmetrics.inc("good.counter")
    return x + 1

jax.jit(traced)

def host(name):
    qmetrics.inc("good.counter")
    qmetrics.inc(GOOD)
    qmetrics.inc("never.declared")
    qmetrics.observe(f"dyn.{name}", 1.0)
'''

METRIC_CLEAN = '''
from oceanbase_tpu.server import metrics as qmetrics

qmetrics.declare("good.counter", "counter", "d")

def host():
    qmetrics.inc("good.counter", verb="x")
'''


def test_obcheck_metric_family_catches_violations():
    from oceanbase_tpu.analysis import Analyzer, check_metric_rules

    az = Analyzer({"pkg/mod.py": METRIC_BAD})
    rules = sorted({f.rule for f in check_metric_rules(az)})
    assert rules == ["metric.dynamic-name", "metric.jit-reachable",
                     "metric.undeclared"]


def test_obcheck_metric_family_quiet_on_clean_and_pragma():
    from oceanbase_tpu.analysis import Analyzer, check_metric_rules

    az = Analyzer({"pkg/mod.py": METRIC_CLEAN})
    assert check_metric_rules(az) == []
    suppressed = METRIC_BAD.replace(
        'qmetrics.inc("never.declared")',
        'qmetrics.inc("never.declared")  # obcheck: ok(metric)')
    az = Analyzer({"pkg/mod.py": suppressed})
    findings = az.filter(check_metric_rules(az))
    assert "metric.undeclared" not in {f.rule for f in findings}


def test_repo_metric_family_clean():
    """The shipped tree must carry ZERO new metric.* findings — the
    family's baseline stays empty (same CI gate as trace/mask/lock)."""
    import os

    from oceanbase_tpu.analysis import (
        diff_findings,
        load_baseline,
        load_package_files,
        run_all,
    )
    from oceanbase_tpu.analysis import check_metric_rules

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = load_package_files(repo)
    findings = run_all(files, (check_metric_rules,))
    new = diff_findings(findings, load_baseline())
    assert not new, "\n".join(f.render() for f in new)


# ---------------------------------------------------------------------------
# plan.join_probes: the executable's static probe counts, added per execution
# ---------------------------------------------------------------------------


def _probe_counts():
    return {kind: qmetrics.counter_value("plan.join_probes", kind=kind)
            for kind in ("merge", "search")}


@pytest.mark.parametrize("path", ["serial", "px"])
def test_join_probes_rise_by_the_executables_counts(path, monkeypatch,
                                                    new_session):
    from oceanbase_tpu.exec import ops
    from oceanbase_tpu.exec.plan import executable_for
    r = np.random.default_rng(5)
    s = new_session()
    s.catalog.load_numpy("jp_a", {"ak": np.arange(400),
                                  "aj": r.integers(0, 50, 400)},
                         primary_key=["ak"])
    s.catalog.load_numpy("jp_b", {"bk": np.arange(300),
                                  "bj": r.integers(0, 50, 300)},
                         primary_key=["bk"])
    s.catalog.load_numpy("jp_c", {"ck": np.arange(50),
                                  "cv": r.integers(0, 9, 50)},
                         primary_key=["ck"])
    if path == "px":
        s.variables["px_dop"] = 4
    sql = ("select count(*), sum(cv) from jp_a join jp_b on aj = bj "
           "join jp_c on bj = ck")
    want = None
    # the same statement compiled on each side of the shape rule: two
    # joins, so two probes of one kind per execution
    for floor, kind, other in ((0, "merge", "search"),
                               (ops._MERGE_PROBE_MIN_GATHERS, "search",
                                "merge")):
        monkeypatch.setattr(ops, "_MERGE_PROBE_MIN_GATHERS", floor)
        executable_for.cache_clear()
        for _ in range(3):
            before = _probe_counts()
            rows = s.execute(sql).rows()
            assert bool(s._last_px) == (path == "px")
            after = _probe_counts()
            assert after[kind] - before[kind] == 2, (kind, before, after)
            assert after[other] == before[other], (kind, before, after)
            want = want or rows
            assert rows == want
    executable_for.cache_clear()


# ---------------------------------------------------------------------------
# plan.groupby_reduces: which way the program's group-bys take (a static
# choice like the probes' kinds), added per execution
# ---------------------------------------------------------------------------


def _groupby_counts():
    return {kind: qmetrics.counter_value("plan.groupby_reduces", kind=kind)
            for kind in ("masked", "sort")}


@pytest.mark.parametrize("path", ["serial", "px"])
@pytest.mark.parametrize("key, kind", [
    ("k", "masked"),    # a dictionary key: no sort, masked reductions
    ("g", "sort"),      # an integer key
])
def test_groupby_reduces_rise_by_the_programs_kinds(key, kind, path,
                                                    new_session):
    r = np.random.default_rng(6)
    s = new_session()
    s.catalog.load_numpy(
        "gr_t", {"id": np.arange(600),
                 "k": r.choice(np.array(["a", "b", "c"]), 600),
                 "g": r.integers(0, 3, 600),
                 "v": r.integers(0, 100, 600)}, primary_key=["id"])
    if path == "px":
        s.variables["px_dop"] = 4
    # the shard program holds the per-shard partial and the final
    # aggregate over the exchanged partials
    per_execution = 2 if path == "px" else 1
    other = "sort" if kind == "masked" else "masked"
    sql = (f"select {key}, sum(v), count(*), min(v) from gr_t "
           f"group by {key} order by {key}")
    want = None
    for _ in range(3):
        before = _groupby_counts()
        rows = s.execute(sql).rows()
        assert bool(s._last_px) == (path == "px")
        after = _groupby_counts()
        assert after[kind] - before[kind] == per_execution, (before, after)
        assert after[other] == before[other], (before, after)
        want = want or rows
        assert rows == want
