"""The deployment ``tpch_sf10_wa5`` at a small size on the CPU: statements
priced over the work area stream ``lineitem`` granule by granule through
``Session.execute`` alone (``exec/granule.py``, ``exec/spill_exec.py``),
and answer as the exact references and the resident plan do.

- Q1, Q6, Q14 (and Q3: a join AND a group-by) over 8 or more granules,
  against ``benchmark/references`` bit for bit and against the resident
  execution of the same session;
- a literal of one type against a column of another: the zone-map bound
  is taken in the COLUMN's stored representation (the parent's tree took
  ``l_quantity < 24`` as ``hi = 24`` on scaled integers, pruned every
  chunk and answered a grouped statement with no rows);
- MVCC through the vectorised provider: a memtable delta, a delete and two
  segments over the snapshot;
- a second execution compiles nothing; a statement that cannot stream is
  counted by its reason and still answers; the granule's buffers are held
  under the work area;
- a chunk program is budgeted for ONE granule (``granule.granule_budget``):
  capacities over the streamed table shrink to the granule's share of the
  estimate and a resident subtree's stay; a table clustered on the filter's
  column overflows ONE granule, the stream stops there, the session re-runs
  once and keeps the factor; the program's cache key holds the budgets; a
  scan with no estimate keeps the plan's capacities.
"""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.harness import spec as bspec  # noqa: E402
from benchmark.harness import traffic as btraffic  # noqa: E402
from oceanbase_tpu.datatypes import SqlType  # noqa: E402
from oceanbase_tpu.exec import granule  # noqa: E402
from oceanbase_tpu.server import Database  # noqa: E402
from oceanbase_tpu.server import metrics as qmetrics  # noqa: E402

SCALE = 0.02
SEED = 4800000007
TABLES = ("lineitem", "part", "orders", "customer")
#: rows the work area holds while a statement streams: granules of
#: 32768 / 4 = 8192 lanes, 15 of them over lineitem's ~120,000 rows;
#: part, orders and customer stay resident under it
STREAM_ROWS = 32768
RESIDENT_ROWS = 1 << 24


def _counter(name: str, **labels) -> float:
    key = qmetrics.series_id(name, labels)
    for n, lbl, v in qmetrics.wire_snapshot()["counters"]:
        if qmetrics.series_id(n, lbl) == key:
            return float(v)
    return 0.0


def _counters(prefix: str) -> float:
    return sum(float(v) for n, _lbl, v in
               qmetrics.wire_snapshot()["counters"] if n.startswith(prefix))


def _sql_types(types: dict, arrays: dict) -> dict:
    return {c: SqlType.decimal(t[1], t[2]) if t[0] == "decimal"
            else SqlType.date() for c, t in types.items() if c in arrays}


@pytest.fixture(scope="module")
def dataset():
    ds = bspec.load_module("datasets", "tpch")
    tables, types = ds.generate(SCALE, SEED)
    return ds, tables, types


@pytest.fixture(scope="module")
def loaded(tmp_path_factory, dataset):
    ds, tables, types = dataset
    db = Database(str(tmp_path_factory.mktemp("wa5") / "db"))
    s = db.session()
    for t in TABLES:
        s.catalog.load_numpy(t, tables[t], types=_sql_types(types, tables[t]),
                             primary_key=ds.PRIMARY_KEYS[t])
    for t in TABLES:
        s.execute(f"analyze table {t}")
    s.execute("set px_dop = 1")
    yield s
    s.close()
    db.close()


def _run(s, sql: str, streamed: bool):
    """-> (result, granules streamed, fall-backs counted)."""
    s.execute("alter system set sql_work_area_rows = "
              f"{STREAM_ROWS if streamed else RESIDENT_ROWS}")
    s._last_spill = None
    g0, f0 = _counter("granule.count"), _counters("spill.fallbacks")
    res = s.execute(sql)
    assert (s._last_spill is not None) == streamed, sql
    return res, _counter("granule.count") - g0, \
        _counters("spill.fallbacks") - f0


def _statement(name: str) -> dict:
    return bspec.read_json(os.path.join(REPO, "benchmark", "statements",
                                        name + ".json"))


# -- (1) the cell's statements ------------------------------------------------

@pytest.mark.parametrize("name", ["tpch_q1_sf10", "tpch_q6", "tpch_q14_sf10"])
def test_streamed_equals_the_exact_reference_and_the_resident_plan(
        loaded, dataset, name):
    _ds, tables, _types = dataset
    st = _statement(name)
    params = btraffic.validation_params(st)
    sql = btraffic.render(st, params)
    ref = bspec.load_module("references", st["reference"]["exact"])
    res, granules, fallbacks = _run(loaded, sql, streamed=True)
    assert granules >= 8 and fallbacks == 0
    # what streams here merges on the device: nothing reached the
    # temp-file store
    spill = loaded._last_spill
    assert (spill.runs, spill.bytes, spill.spilled_rows) == (0, 0, 0)
    got = ref.extract(list(res.names), res.arrays)
    want = ref.answer(tables, params)
    if isinstance(want, float):
        assert float(got) == want       # bit-equal off the chip
    assert got == want and want not in (None, {}, 0)
    resident, none, _ = _run(loaded, sql, streamed=False)
    assert none == 0 and res.rows() == resident.rows()


def test_q3_streams_its_join_and_group_by(loaded):
    """Q3: lineitem streams and probes the resident join of customer and
    orders; its group-by has a group an order, so the partial states
    outgrow this budget and merge by key on the host half."""
    st = _statement("tpch_q3")
    sql = btraffic.render(st, btraffic.validation_params(st))
    res, granules, fallbacks = _run(loaded, sql, streamed=True)
    assert granules >= 8 and fallbacks == 0
    assert "groupby" in loaded._last_spill.kind \
        and "join" in loaded._last_spill.kind
    resident, _, _ = _run(loaded, sql, streamed=False)
    assert len(res.rows()) == 10 and res.rows() == resident.rows()


# -- (2) zone-map bounds in the column's representation -----------------------

_COLUMNS = {"decimal": "l_quantity", "int": "l_suppkey",
            "date": "l_shipdate"}
_LITERALS = {
    "decimal": {"int": "24", "decimal3": "24.125", "decimal1": "24.5"},
    "int": {"int": "100", "decimal3": "100.000", "decimal1": "99.5"},
    "date": {"date": "date '1995-06-17'"},
}
_OPS = ("<", "<=", ">", ">=", "=", "between")


def _bound_cases():
    for kind, col in _COLUMNS.items():
        for lit_kind, lit in _LITERALS[kind].items():
            for op in _OPS:
                pred = f"{col} between {lit} and {lit}" if op == "between" \
                    else f"{col} {op} {lit}"
                yield pytest.param(pred, id=f"{kind}-{lit_kind}-{op}")


@pytest.mark.parametrize("pred", list(_bound_cases()))
def test_a_literal_against_a_column_of_another_type(loaded, pred):
    sql = (f"select l_returnflag, count(*) as n, sum(l_extendedprice) as s "
           f"from lineitem where {pred} group by l_returnflag "
           f"order by l_returnflag")
    res, granules, fallbacks = _run(loaded, sql, streamed=True)
    resident, _, _ = _run(loaded, sql, streamed=False)
    assert fallbacks == 0 and granules >= 1
    assert res.rows() == resident.rows()


def test_the_statement_the_parent_answered_with_no_rows(loaded):
    sql = ("select l_returnflag, count(*) from lineitem "
           "where l_quantity < 24 group by l_returnflag")
    res, granules, _ = _run(loaded, sql, streamed=True)
    resident, _, _ = _run(loaded, sql, streamed=False)
    assert granules >= 8
    assert sorted(res.rows()) == sorted(resident.rows())
    assert len(res.rows()) == 3 and all(n > 0 for _f, n in res.rows())


def test_a_bound_that_excludes_every_chunk(loaded):
    """No granule: the statement answers as the resident plan does over no
    rows, through the tier (it does not fall back)."""
    for sql in ("select count(*), sum(l_quantity) from lineitem "
                "where l_quantity > 1000",
                "select l_returnflag, count(*) from lineitem "
                "where l_shipdate < date '1970-01-01' group by l_returnflag"):
        p0 = _counter("granule.pruned_chunks")
        res, granules, fallbacks = _run(loaded, sql, streamed=True)
        resident, _, _ = _run(loaded, sql, streamed=False)
        assert granules == 0 and fallbacks == 0
        assert _counter("granule.pruned_chunks") > p0
        assert res.rows() == resident.rows()


def test_the_bound_is_the_columns_stored_integer():
    from oceanbase_tpu.exec.plan import Filter, TableScan
    from oceanbase_tpu.expr import ir

    types = {"q": SqlType.decimal(15, 2), "k": SqlType.int_(),
             "d": SqlType.date()}
    scan = TableScan("t", rename={"q": "t_q_0", "k": "t_k_1", "d": "t_d_2"})

    def bounds(pred):
        return granule.extract_column_bounds(Filter(scan, pred), types, "t")

    dec = SqlType.decimal
    assert bounds(ir.col("t_q_0") < ir.lit(24)) == {"q": (None, 2400)}
    assert bounds(ir.col("t_q_0") >= ir.lit("0.5", dec())) == \
        {"q": (50, None)}
    assert bounds(ir.col("t_q_0") < ir.lit("24.120", dec())) == \
        {"q": (None, 2412)}
    # not exact at the column's scale: the conjunct prunes nothing
    assert bounds(ir.col("t_q_0") < ir.lit("24.125", dec())) == {}
    assert bounds(ir.col("t_k_1") <= ir.lit("3.00", dec())) == \
        {"k": (None, 3)}
    assert bounds(ir.col("t_k_1") <= ir.lit("2.5", dec())) == {}
    assert bounds(ir.col("t_d_2") > ir.lit(9000)) == {}     # days? no
    assert bounds(ir.col("t_d_2") > ir.lit("1995-06-17", SqlType.date())) \
        == {"d": (9298, None)}
    # a column another table's scan gives is not this table's
    other = TableScan("u", rename={"q": "u_q_0"})
    assert granule.extract_column_bounds(
        Filter(other, ir.col("u_q_0") < ir.lit(24)), types, "t") == {}


# -- (3) MVCC through the vectorised provider -----------------------------------

def test_a_delta_a_delete_and_two_segments_equal_the_resident_read(tmp_path):
    db = Database(str(tmp_path / "db"))
    s = db.session()
    n = 3000
    rng = np.random.default_rng(11)
    flags = np.array(["A", "N", "R"], dtype=object)[rng.integers(0, 3, n)]
    s.catalog.load_numpy(
        "t", {"k": np.arange(n, dtype=np.int64),
              "g": flags,
              "q": rng.integers(100, 5000, n).astype(np.int64),
              "d": rng.integers(9000, 9400, n).astype(np.int32)},
        types={"q": SqlType.decimal(15, 2), "d": SqlType.date()},
        primary_key=["k"])
    s.execute("analyze table t")
    # a second segment over the load: updates, a delete, new rows, a new
    # string for the dictionary
    s.execute("update t set q = q + 1000 where k < 40")
    s.execute("delete from t where k >= 100 and k < 140")
    s.execute("insert into t values " + ", ".join(
        f"({n + i}, 'Z', {i}.25, date '1995-07-{1 + i % 28:02d}')"
        for i in range(60)))
    db.checkpoint()
    # and a memtable delta on top of both
    s.execute("update t set g = 'Q' where k >= 40 and k < 50")
    s.execute("delete from t where k = 7")
    s.execute(f"insert into t values ({n + 500}, 'A', 1.00, "
              "date '1995-08-01')")
    tablet = db.engine.tables["t"].tablet
    assert len(tablet.segments) == 2 and len(tablet.active) > 0
    statements = [
        "select g, count(*), sum(q), min(d), max(k) from t group by g "
        "order by g",
        "select count(*), sum(q) from t where q < 24",
        "select count(*), sum(k) from t where d >= date '1995-07-01'",
        "select k, q from t where k < 60 order by k",
    ]
    for sql in statements:
        s.execute(f"alter system set sql_work_area_rows = {RESIDENT_ROWS}")
        s._last_spill = None
        want = s.execute(sql).rows()
        assert s._last_spill is None
        s.execute("alter system set sql_work_area_rows = 1024")
        f0 = _counters("spill.fallbacks")
        got = s.execute(sql).rows()
        assert s._last_spill is not None and \
            _counters("spill.fallbacks") == f0
        assert got == want, sql
    # the provider alone: one snapshot, the rows snapshot_arrays reads
    snap = db.tx.gts.current()
    want_a, _ = tablet.snapshot_arrays(snap)
    prov = granule.segment_chunk_provider(tablet, snap)
    dicts = prov.string_dicts(["g"])
    got_k, got_g = [], []
    for arrays, _valids in prov("t", 256, None, ["k", "g"]):
        assert set(arrays) == {"k", "g"} and len(arrays["k"]) <= 256
        got_k.append(arrays["k"])
        got_g.append(dicts["g"].values[arrays["g"].codes])
    order = np.argsort(np.concatenate(got_k))
    want_order = np.argsort(want_a["k"])
    np.testing.assert_array_equal(np.concatenate(got_k)[order],
                                  want_a["k"][want_order])
    np.testing.assert_array_equal(
        np.concatenate(got_g)[order].astype(str),
        want_a["g"][want_order].astype(str))
    s.close()
    db.close()


def test_a_string_key_and_a_composite_key_under_a_delta(tmp_path):
    """A base row goes when a newer part holds its key: keys compared by
    value, a dictionary-coded string key and a two-column key alike."""
    db = Database(str(tmp_path / "db"))
    s = db.session()
    n = 2000
    s.catalog.load_numpy(
        "u", {"name": np.array([f"n{i:05d}" for i in range(n)], dtype=object),
              "v": np.arange(n, dtype=np.int64)}, primary_key=["name"])
    s.catalog.load_numpy(
        "w", {"a": np.repeat(np.arange(n // 4, dtype=np.int64), 4),
              "b": np.tile(np.arange(4, dtype=np.int64), n // 4),
              "v": np.arange(n, dtype=np.int64)}, primary_key=["a", "b"])
    s.execute("update u set v = v + 100000 where name < 'n00020'")
    s.execute("delete from u where name = 'n00500'")
    s.execute("insert into u values ('zz', 7)")
    s.execute("update w set v = v + 100000 where a = 3")
    s.execute("delete from w where a = 5 and b = 2")
    s.execute("insert into w values (100000, 0, 1)")
    for sql in ("select count(*), sum(v), min(name), max(name) from u",
                "select count(*), sum(v), sum(a * 10 + b) from w"):
        s.execute(f"alter system set sql_work_area_rows = {RESIDENT_ROWS}")
        want = s.execute(sql).rows()
        s.execute("alter system set sql_work_area_rows = 1024")
        s._last_spill = None
        assert s.execute(sql).rows() == want, sql
        assert s._last_spill is not None
    s.close()
    db.close()


def test_the_base_segment_decodes_only_what_is_asked(loaded):
    """One bulk-loaded segment and no memtable row over the snapshot: no
    key column, no unreached column, strings as codes of one dictionary."""
    tablet = loaded.db.engine.tables["lineitem"].tablet
    snap = loaded.db.tx.gts.current()
    prov = granule.segment_chunk_provider(tablet, snap)
    names = ["l_returnflag", "l_quantity"]
    dicts = prov.string_dicts(names)
    assert list(dicts) == ["l_returnflag"]
    assert list(dicts["l_returnflag"].values) == ["A", "N", "R"]
    rows = 0
    for arrays, valids in prov("lineitem", 8192, None, names):
        assert list(arrays) == names
        assert arrays["l_returnflag"].values is dicts["l_returnflag"].values
        assert arrays["l_returnflag"].codes.dtype == np.int32
        assert arrays["l_quantity"].dtype == np.int64
        assert all(v is None for v in valids.values())
        rows += len(arrays["l_quantity"])
    assert rows == tablet.segments[0].n_rows


# -- (4) compiles, fall-backs, the budget ------------------------------------------

def test_a_second_execution_compiles_nothing(loaded):
    import jax

    events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _sec, **_kw: events.append(name)
        if "compil" in name or "trac" in name or "lower" in name else None)
    for name in ("tpch_q1_sf10", "tpch_q6", "tpch_q14_sf10"):
        st = _statement(name)
        sql = btraffic.render(st, btraffic.validation_params(st))
        _run(loaded, sql, streamed=True)
        c0 = _counter("plan.compiles")
        del events[:]
        res, granules, _ = _run(loaded, sql, streamed=True)
        assert granules >= 8 and res.rows()
        assert events == [] and _counter("plan.compiles") == c0, name
    # the chunk programs are rows of the executable cache
    texts = [r[0] for r in loaded.execute(
        "select plan_text from gv$plan_cache").rows()]
    assert sum(t.startswith("granule(lanes=8192)") for t in texts) >= 3


def test_a_statement_that_cannot_stream_says_why_and_answers(loaded):
    sql = "select count(distinct l_suppkey) from lineitem"
    s = loaded
    s.execute(f"alter system set sql_work_area_rows = {STREAM_ROWS}")
    f0 = _counters("spill.fallbacks")
    d0 = _counter("sql.work_area_decisions", kind="spill")
    s._last_spill = None
    got = s.execute(sql).rows()
    assert _counter("sql.work_area_decisions", kind="spill") == d0 + 1
    assert _counters("spill.fallbacks") == f0 + 1 and s._last_spill is None
    reasons = [lbl for n, lbl, v in qmetrics.wire_snapshot()["counters"]
               if n == "spill.fallbacks" and v]
    assert all(lbl.get("reason") for lbl in reasons)
    trace = [r[0].strip() for r in s.execute("show trace").rows()]
    assert "spill.fallback" in trace
    s.execute(f"alter system set sql_work_area_rows = {RESIDENT_ROWS}")
    assert got == s.execute(sql).rows()


def test_the_spans_and_counters_of_a_streamed_statement(loaded):
    st = _statement("tpch_q6")
    sql = btraffic.render(st, btraffic.validation_params(st))
    before = {n: _counter(n) for n in ("granule.count", "granule.rows",
                                       "granule.upload_bytes")}
    _res, granules, _ = _run(loaded, sql, streamed=True)
    names = [r[0].strip() for r in loaded.execute("show trace").rows()]
    for span in ("spill.execute", "granule.fetch", "granule.upload",
                 "granule.program", "granule.merge"):
        assert span in names, span
    assert names.count("granule.program") == granules
    assert names.count("granule.upload") == granules
    n_rows = loaded.db.engine.tables["lineitem"].tablet.segments[0].n_rows
    assert _counter("granule.count") - before["granule.count"] == granules
    assert _counter("granule.rows") - before["granule.rows"] == n_rows
    # Q6 reaches four columns: 8 + 8 + 8 + 4 bytes a row and the row mask
    assert _counter("granule.upload_bytes") - before["granule.upload_bytes"] \
        == granules * 8192 * (8 + 8 + 8 + 4 + 1)


def test_the_granule_is_sized_under_the_work_area():
    assert granule.BUFFERS_IN_FLIGHT == granule.PREFETCH_DEPTH + 2
    assert granule.granule_rows_for(STREAM_ROWS) == 8192
    assert granule.granule_rows_for(4096) == 1024
    # 5 % of a v5e's 16.9 GB over Q1's 45 B a row: the default granule
    assert granule.granule_rows_for(840_000_000 // 45) == 1 << 21
    for rows in (100, 1000, 5000, 1 << 20, 1 << 30):
        g = granule.granule_rows_for(rows)
        assert g * granule.BUFFERS_IN_FLIGHT <= max(
            rows, 64 * granule.BUFFERS_IN_FLIGHT)
        assert g <= granule.DEFAULT_CHUNK_ROWS


def test_buffers_over_the_work_area_are_refused(loaded):
    from oceanbase_tpu.exec.plan import ScalarAgg, TableScan
    from oceanbase_tpu.exec.ops import AggSpec
    from oceanbase_tpu.expr import ir

    tablet = loaded.db.engine.tables["lineitem"].tablet
    prov = granule.segment_chunk_provider(tablet,
                                          loaded.db.tx.gts.current())
    types = {c.name: c.dtype
             for c in loaded.db.engine.tables["lineitem"].tdef.columns}
    plan = ScalarAgg(TableScan("lineitem", rename={
        "l_quantity": "q"}), [AggSpec("s", "sum", ir.col("q"))])
    with pytest.raises(AssertionError, match="over the work area"):
        granule.execute_streamed(plan, prov, chunk_rows=8192, types=types,
                                 budget_bytes=8192 * 9 * 3)
    out = granule.execute_streamed(plan, prov, chunk_rows=8192, types=types,
                                   budget_bytes=8192 * 10 * 4)
    from oceanbase_tpu.vector import to_numpy

    assert to_numpy(out)["s"][0] > 0


# -- (5) a granule's budget --------------------------------------------------------

@pytest.mark.parametrize("capacity, chunk_rows, share, within, want", [
    # Q14 at SF10: the month's filter in a 2,097,152-lane bucket, a granule
    # of 2,097,152 lanes of 60.0M rows: 73,300 -> the ladder's 131,072
    (1 << 21, 1 << 21, (1 << 21) / 59_986_052, True, 1 << 17),
    # other statistics, one rung lower
    (1 << 20, 1 << 21, (1 << 21) / 59_986_052, True, 1 << 16),
    # a join's output is not bound by the granule's lanes, a Compact's is
    (1 << 24, 1 << 12, 0.5, False, 1 << 23),
    (1 << 24, 1 << 12, 0.5, True, 1 << 12),
    # never above the node's own capacity; the ladder's floor
    (100, 8192, 0.9, True, 100),
    (4096, 8192, 1 / 1024, True, 64),
    # a table that fits one granule: the share is the whole
    (4096, 8192, 1.0, True, 4096),
    # what does not divide among granules (a group-by's groups): the
    # granule's lanes bound it and nothing else
    (64, 8192, None, True, 64),
    (1 << 20, 8192, None, True, 8192),
])
def test_a_granules_budget(capacity, chunk_rows, share, within, want):
    assert granule.granule_budget(capacity, chunk_rows, share,
                                  within_granule=within) == want


def _nodes(plan, kind):
    from oceanbase_tpu.exec import plan as pp

    return [n for n in pp._postorder(plan) if isinstance(n, kind)]


@pytest.mark.parametrize("name", ["tpch_q14_sf10", "tpch_q3"])
def test_the_chunk_program_is_budgeted_for_one_granule(loaded, dataset, name):
    """Capacities over ``lineitem`` are the granule's share of the plan's,
    a subtree over resident tables keeps its own (Q3's join of customer
    and orders), the counters say both sums an execution, and the answer
    is the reference's and the resident plan's."""
    from oceanbase_tpu.exec import plan as pp
    from oceanbase_tpu.sql.parser import parse_sql

    _ds, tables, _types = dataset
    st = _statement(name)
    params = btraffic.validation_params(st)
    sql = btraffic.render(st, params)
    plan, _outs, _est = loaded._plan_select(parse_sql(sql), None)
    lanes = granule.granule_rows_for(STREAM_ROWS)
    gp = granule.GranulePlan(plan, "lineitem", lanes)
    (scan,) = [n for n in _nodes(plan, pp.TableScan)
               if n.table == "lineitem"]
    share = lanes / scan.est_rows
    assert 1 / 16 < share < 1 / 14
    lowered = given = 0
    for kind, field in ((pp.Compact, "capacity"),
                        (pp.HashJoin, "out_capacity")):
        for was, now in zip(_nodes(plan, kind), _nodes(gp.chunk, kind)):
            cap = getattr(was, field)
            if "lineitem" not in pp.referenced_tables(was):
                assert now is was       # the resident subtree, untouched
                continue
            want = min(cap, granule.bucket_capacity(int(cap * share) + 1))
            assert getattr(now, field) == want < cap
            lowered, given = lowered + want, given + cap
    assert (gp.budget_lanes, gp.plan_budget_lanes) == (lowered, given)
    assert 0 < lowered * 8 <= given
    if name == "tpch_q14_sf10":
        (compact,) = _nodes(gp.chunk, pp.Compact)
        assert compact.strict and compact.capacity == 512
    else:
        resident = [n for n in _nodes(plan, pp.HashJoin)
                    if "lineitem" not in pp.referenced_tables(n)]
        assert resident and all(n.out_capacity for n in resident)
    b0 = _counter("granule.budget_lanes")
    p0 = _counter("granule.plan_budget_lanes")
    res, granules, fallbacks = _run(loaded, sql, streamed=True)
    assert granules >= 8 and fallbacks == 0
    assert _counter("granule.budget_lanes") - b0 == granules * lowered
    assert _counter("granule.plan_budget_lanes") - p0 == granules * given
    resident_res, _, _ = _run(loaded, sql, streamed=False)
    assert res.rows() == resident_res.rows()
    if name == "tpch_q14_sf10":
        ref = bspec.load_module("references", st["reference"]["exact"])
        assert ref.extract(list(res.names), res.arrays) == \
            ref.answer(tables, params)


def test_q1_and_q6_have_no_node_to_budget(loaded):
    """No ``Compact``, no join: the counters stay, Q1's group-by keeps its
    capacity under the granule's lanes (groups do not divide)."""
    from oceanbase_tpu.exec import plan as pp
    from oceanbase_tpu.sql.parser import parse_sql

    for name in ("tpch_q1_sf10", "tpch_q6"):
        st = _statement(name)
        sql = btraffic.render(st, btraffic.validation_params(st))
        plan, _outs, _est = loaded._plan_select(parse_sql(sql), None)
        gp = granule.GranulePlan(plan, "lineitem", 8192)
        assert (gp.budget_lanes, gp.plan_budget_lanes) == (0, 0)
        if gp.group is not None:
            assert gp.chunk.out_capacity == min(gp.group.out_capacity, 8192)
        b0 = _counter("granule.plan_budget_lanes")
        _run(loaded, sql, streamed=True)
        assert _counter("granule.plan_budget_lanes") == b0


def test_a_clustered_table_costs_one_restart_and_is_remembered(tmp_path):
    """Every survivor of the filter lies in ONE granule (the table is
    clustered on the filter's column), sixteen times that granule's share:
    the stream stops at that granule, the session re-plans ONCE by what
    was dropped, answers exactly, and starts the next execution at the
    factor that cleared."""
    db = Database(str(tmp_path / "db"))
    s = db.session()
    n = 65536
    rng = np.random.default_rng(49)
    k = np.arange(n, dtype=np.int64)
    fk = rng.integers(0, 1000, n).astype(np.int64)
    v = rng.integers(1, 100, n).astype(np.int64)
    w = rng.integers(1, 10, 1000).astype(np.int64)
    s.catalog.load_numpy("fact", {"k": k, "d": k // 64, "fk": fk, "v": v},
                         primary_key=["k"])
    s.catalog.load_numpy("dim", {"pk": np.arange(1000, dtype=np.int64),
                                 "w": w}, primary_key=["pk"])
    s.execute("analyze table fact")
    s.execute("analyze table dim")
    s.execute("set px_dop = 1")
    sql = ("select sum(f.v * d.w), count(*) from fact f, dim d "
           "where f.fk = d.pk and f.d >= 100 and f.d < 116")
    live = (k // 64 >= 100) & (k // 64 < 116)
    want = [(int((v[live] * w[fk[live]]).sum()), int(live.sum()))]
    assert want[0][1] == 1024
    # 16 granules of 4,096 lanes; the survivors are rows 6,400-7,423: all
    # in the second, whose budget is a sixteenth of the plan's 2,048
    s.execute("alter system set sql_work_area_rows = 16384")

    def execute():
        r0 = _counter("plan.capacity_retries")
        s._last_spill = None
        rows = s.execute(sql).rows()
        assert s._last_spill is not None
        programs = [r[0].strip() for r in s.execute("show trace").rows()
                    ].count("granule.program")
        return rows, _counter("plan.capacity_retries") - r0, programs

    rows, retries, programs = execute()
    assert rows == want
    # two programs until the overflow (not sixteen), then the whole table
    assert (retries, programs) == (1, 2 + 16)
    assert list(s._spill_factors.values()) == [16]
    rows, retries, programs = execute()
    assert (rows, retries, programs) == (want, 0, 16)
    s.execute("alter system set sql_work_area_rows = 16777216")
    s._last_spill = None
    assert s.execute(sql).rows() == want and s._last_spill is None
    s.close()
    db.close()


def _compacted_sum(est_rows):
    """sum(v) over a strict ``Compact`` of the rows with ``v < 40`` of a
    scan estimated at ``est_rows`` rows."""
    from oceanbase_tpu.exec.ops import AggSpec
    from oceanbase_tpu.exec.plan import Compact, Filter, ScalarAgg, TableScan
    from oceanbase_tpu.expr import ir

    scan = TableScan("t", rename={"v": "t_v_0"}, est_rows=est_rows)
    return ScalarAgg(Compact(Filter(scan, ir.col("t_v_0") < ir.lit(40)),
                             capacity=4096, strict=True),
                     [AggSpec("s", "sum", ir.col("t_v_0"))])


def test_the_chunk_programs_key_holds_its_budgets():
    """Two executions whose statistics differ enough to change the bucket
    share a plan fingerprint (``est_rows`` is outside it) and NOT a chunk
    program; equal statistics share one.  A scan with no estimate keeps
    the plan's capacities."""
    from oceanbase_tpu.exec.plan import Compact
    from oceanbase_tpu.vector import to_numpy

    n = 16384
    v = (np.arange(n, dtype=np.int64) * 7919) % 1000
    prov = granule.numpy_chunk_provider({"v": v})
    types = {"v": SqlType.int_()}
    small, large, none = (_compacted_sum(e) for e in (n, 64 * n, None))
    assert small.fingerprint() == large.fingerprint() == none.fingerprint()
    plans = {e: granule.GranulePlan(p, "t", 1024)
             for e, p in (("small", small), ("large", large), ("none", none))}
    caps = {e: _nodes(gp.chunk, Compact)[0].capacity
            for e, gp in plans.items()}
    # 1,024 of 16,384 rows: a sixteenth of 4,096; of 1M rows: the floor;
    # no estimate: the plan's own
    assert caps == {"small": 256, "large": 64, "none": 4096}
    assert plans["none"].inner is none.child
    # a scan pipeline inside a larger plan (the host half's walk) is
    # budgeted the same way
    sub = granule.GranulePlan(small.child, "t", 1024, subtree=True)
    assert sub.chunk.capacity == 256 and not sub.aggregates
    assert (plans["none"].budget_lanes, plans["none"].plan_budget_lanes) \
        == (0, 0)
    exes = {e: gp.chunk_executable() for e, gp in plans.items()}
    assert len({id(x) for x in exes.values()}) == 3
    assert granule.GranulePlan(_compacted_sum(n + 1), "t", 1024) \
        .chunk_executable() is exes["small"]
    # three rows of gv$plan_cache, each under the granule's lanes
    assert len({x.stats.plan_hash for x in exes.values()}) == 3
    assert all(x.stats.plan_text.startswith("granule(lanes=1024) ")
               for x in exes.values())
    # 40 of every 1,000 values survive: 41 a granule, so each budget holds
    want = int(v[v < 40].sum())
    for plan in (small, large, none):
        out = granule.execute_streamed(plan, prov, chunk_rows=1024,
                                       types=types)
        assert int(to_numpy(out)["s"][0]) == want
