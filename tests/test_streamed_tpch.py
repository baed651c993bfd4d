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
  under the work area.
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
