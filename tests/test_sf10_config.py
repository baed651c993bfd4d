"""The deployment ``tpch_sf10`` at a small size on the CPU: the statements
of its cell through ``Session.execute`` on a database loaded by the direct
load, under ``set global ob_sql_work_area_percentage = 80``, against the
benchmark's exact references; the work area as a share of the device's
memory; and the load itself: one device copy a table, equal to what the
store rebuilds, statistics equal to the host's, durable.
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
from oceanbase_tpu.exec.plan import TableScan  # noqa: E402
from oceanbase_tpu.server import Database  # noqa: E402
from oceanbase_tpu.server import config as qconfig  # noqa: E402
from oceanbase_tpu.server import metrics as qmetrics  # noqa: E402
from oceanbase_tpu.sql import table_stats  # noqa: E402

SCALE = 0.02
SEED = 3800000021
TABLES = ("lineitem", "part")


def _counter(name: str, **labels) -> float:
    key = qmetrics.series_id(name, labels)
    for n, lbl, v in qmetrics.wire_snapshot()["counters"]:
        if qmetrics.series_id(n, lbl) == key:
            return float(v)
    return 0.0


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
    """The configuration's set-up as ``harness/adapter.py`` does it:
    ``Database(root)``, its ``system_settings``, ``load_numpy`` and
    ``ANALYZE`` per table, its ``session_settings``."""
    ds, tables, types = dataset
    cfg = bspec.read_json(os.path.join(REPO, "benchmark", "configs",
                                       "tpch_sf10.json"))
    root = str(tmp_path_factory.mktemp("sf10") / "db")
    db = Database(root)
    s = db.session()
    for sql in cfg["system_settings"]:
        s.execute(sql)
    builds0 = _counter("storage.device_copy_builds")
    for t in TABLES:
        s.catalog.load_numpy(t, tables[t], types=_sql_types(types, tables[t]),
                             primary_key=ds.PRIMARY_KEYS[t])
    for t in TABLES:
        s.execute(f"analyze table {t}")
    for sql in cfg["session_settings"]:
        s.execute(sql)
    yield {"db": db, "session": s, "root": root, "builds0": builds0}
    s.close()
    db.close()


def _statement(name: str) -> dict:
    return bspec.read_json(os.path.join(REPO, "benchmark", "statements",
                                        name + ".json"))


def _cases():
    out = [("tpch_q1_sf10", None), ("tpch_q14_sf10", None)]
    q6 = _statement("tpch_q6")
    out += [("tpch_q6", p) for p in btraffic.draw_pool("tpch_q6", q6, 3,
                                                       SEED)]
    return out


@pytest.mark.parametrize("name,params", _cases(), ids=lambda v: (
    v if isinstance(v, str) else "validation" if v is None
    else "-".join(v.values())))
def test_the_cells_statements_equal_their_exact_references(
        loaded, dataset, name, params):
    _ds, tables, _types = dataset
    st = _statement(name)
    params = params or btraffic.validation_params(st)
    assert st["reference"] == {"sqlite": False,
                               "exact": st["reference"]["exact"]}
    ref = bspec.load_module("references", st["reference"]["exact"])
    res = loaded["session"].execute(btraffic.render(st, params))
    got = ref.extract(list(res.names), res.arrays)
    want = ref.answer(tables, params)
    if isinstance(want, float):
        assert float(got) == want       # bit-equal off the chip
    assert got == want and want not in (None, {}, 0)


def test_the_sf10_statements_are_the_sf1_ones_letter_for_letter():
    for sf10, sf1 in (("tpch_q1_sf10", "tpch_q1"),
                      ("tpch_q14_sf10", "tpch_q14")):
        a, b = _statement(sf10), _statement(sf1)
        for key in ("sql", "parameters", "reads"):
            assert a[key] == b[key], (sf10, key)
    cfg = bspec.read_json(os.path.join(REPO, "benchmark", "configs",
                                       "tpch_sf10.json"))
    sf1 = bspec.read_json(os.path.join(REPO, "benchmark", "configs",
                                       "tpch_sf1.json"))
    assert cfg["system_settings"] == \
        ["set global ob_sql_work_area_percentage = 80"]
    for key in ("isolation", "durability"):
        assert cfg["guarantees"][key] == sf1["guarantees"][key]


def test_one_device_copy_a_loaded_table_and_none_at_the_first_read(loaded):
    s = loaded["session"]
    for t in TABLES:
        s.catalog.table_data(t)
    s.execute("select count(*) from lineitem, part where l_partkey = p_partkey")
    assert _counter("storage.device_copy_builds") - loaded["builds0"] \
        == len(TABLES)
    resident = sum(int(r[1]) for r in s.execute(
        "select stat_name, value from gv$sysstat "
        "where stat_name = 'storage.device_copy_bytes'").rows())
    rels = [s.catalog.table_data(t) for t in TABLES]
    from oceanbase_tpu.share.kvcache import relation_bytes

    assert resident >= sum(relation_bytes(r) for r in rels) > 0


def test_the_loaded_copy_is_what_the_store_rebuilds(loaded):
    """The relation the load registered against the one ``_device_copy``
    decodes from the segment: lanes, codes, dictionaries, mask."""
    cat = loaded["session"].catalog
    for t in TABLES:
        first = cat.table_data(t)
        cat.invalidate(t)
        again = cat.table_data(t)
        assert again is not first and again.capacity == first.capacity
        assert list(again.columns) == list(first.columns)
        assert np.array_equal(np.asarray(again.mask), np.asarray(first.mask))
        for c, col in first.columns.items():
            other = again.columns[c]
            assert col.dtype == other.dtype, c
            assert np.array_equal(np.asarray(col.data),
                                  np.asarray(other.data)), c
            assert (col.sdict is None) == (other.sdict is None)
            if col.sdict is not None:
                assert col.sdict == other.sdict
                assert list(col.sdict.values) == list(other.sdict.values)


@pytest.mark.parametrize("percent,kind", [(1, "spill"), (80, "resident")])
def test_the_percentage_prices_a_statement_in_bytes(loaded, dataset,
                                                    monkeypatch, percent,
                                                    kind):
    """At SF 0.02 Q1 reaches 119,000 rows x 45 B = 5.4 MB of lineitem: over
    1 % of a 64 MiB device, under 80 % of it.  The answers are equal."""
    _ds, tables, _types = dataset
    monkeypatch.setattr(qconfig, "device_bytes_limit", lambda: 64 << 20)
    s = loaded["session"]
    s.execute(f"set global ob_sql_work_area_percentage = {percent}")
    try:
        for name in ("tpch_q1_sf10", "tpch_q6"):
            st = _statement(name)
            params = btraffic.validation_params(st)
            ref = bspec.load_module("references", st["reference"]["exact"])
            before = {k: _counter("sql.work_area_decisions", kind=k)
                      for k in ("spill", "resident")}
            s._last_spill = None
            res = s.execute(btraffic.render(st, params))
            assert ref.extract(list(res.names), res.arrays) \
                == ref.answer(tables, params)
            after = {k: _counter("sql.work_area_decisions", kind=k)
                     for k in ("spill", "resident")}
            other = "resident" if kind == "spill" else "spill"
            assert after[kind] - before[kind] == 1
            assert after[other] == before[other]
            if name == "tpch_q1_sf10":
                # the group-by streams through the spill tier; Q6's
                # scalar aggregate is priced alike and then falls back
                # to the device (``NotDistributable``), as at the parent
                assert (s._last_spill is not None) == (kind == "spill")
        gauge = dict(s.execute(
            "select stat_name, value from gv$sysstat "
            "where stat_name = 'sql.work_area_bytes'").rows())
        assert gauge["sql.work_area_bytes"] == (64 << 20) * percent // 100
        shown = dict(s.execute("show parameters").rows())
        assert shown["ob_sql_work_area_percentage"] == str(percent)
    finally:
        s.execute("set global ob_sql_work_area_percentage = 80")


def test_the_row_knob_decides_while_it_is_not_zero(tmp_path):
    """One pricing (the rows of a table the work area holds); which budget
    is an explicit value: the percentage's bytes unless
    ``sql_work_area_rows`` is not 0, and then that many rows.  Setting the
    percentage to its default changes nothing, and 0 gives the decision
    back."""
    db = Database(str(tmp_path / "db"))
    s = db.session()
    try:
        s.execute("create table t (k int primary key, v int, w double)")
        plan = TableScan("t")
        share = qconfig.device_bytes_limit() * 5 // 100
        # three 8-byte columns with their validity, and the row mask
        row_bytes = 3 * (8 + 1) + 1
        assert int(db.config["sql_work_area_rows"]) == 0
        assert qconfig.work_area_bytes(s.tenant.config) == share
        assert s._work_area(plan)("t") == share // row_bytes
        s.execute("alter system set ob_sql_work_area_percentage = 5")
        assert s._work_area(plan)("t") == share // row_bytes
        s.execute("alter system set sql_work_area_rows = 1000")
        assert s._work_area(plan)("t") == 1000
        s.execute("alter system set sql_work_area_rows = 0")
        assert s._work_area(plan)("t") == share // row_bytes
        with pytest.raises(ValueError):
            s.execute("set global ob_sql_work_area_percentage = 0")
    finally:
        s.close()
        db.close()


def _small_rows(n=3000):
    rng = np.random.default_rng(7)
    k = rng.permutation(n).astype(np.int64)
    return {"k": k, "g": (k % 17).astype(np.int64),
            "d": (9000 + k % 400).astype(np.int32),
            "m": (k * 37 % 100000).astype(np.int64),
            "s": np.array([f"name-{i % 41:03d}" for i in k], dtype=object)}


@pytest.fixture(scope="module")
def two_ways(tmp_path_factory):
    """The same rows by ``INSERT`` and by the direct load."""
    root = str(tmp_path_factory.mktemp("two_ways") / "db")
    db = Database(root)
    s = db.session()
    rows = _small_rows()
    ddl = ("(k bigint not null, g bigint not null, d date not null, "
           "m decimal(15,2) not null, s varchar(16) not null, "
           "primary key (k))")
    s.execute("create table by_insert " + ddl)
    s.execute("create table by_load " + ddl)
    for lo in range(0, len(rows["k"]), 500):
        values = ", ".join(
            "({}, {}, date '{}', {}.{:02d}, '{}')".format(
                rows["k"][i], rows["g"][i],
                np.datetime64(int(rows["d"][i]), "D"),
                rows["m"][i] // 100, rows["m"][i] % 100, rows["s"][i])
            for i in range(lo, min(lo + 500, len(rows["k"]))))
        s.execute("insert into by_insert values " + values)
    s.catalog.load_numpy(
        "by_load", rows, types={"d": SqlType.date(),
                                "m": SqlType.decimal(15, 2)},
        primary_key=["k"])
    for t in ("by_insert", "by_load"):
        s.execute(f"analyze table {t}")
    yield db, s, root, rows
    s.close()
    db.close()


def test_a_loaded_table_equals_the_same_rows_inserted(two_ways):
    _db, s, _root, rows = two_ways
    a, b = (s.catalog.table_data(t) for t in ("by_insert", "by_load"))
    assert a.capacity == b.capacity and list(a.columns) == list(b.columns)
    assert np.array_equal(np.asarray(a.mask), np.asarray(b.mask))
    live = np.asarray(a.mask)
    for c in a.columns:
        assert np.array_equal(np.asarray(a.columns[c].data)[live],
                              np.asarray(b.columns[c].data)[live]), c
        if a.columns[c].sdict is not None:
            assert a.columns[c].sdict == b.columns[c].sdict
    ta, tb = (s.catalog.table_def(t) for t in ("by_insert", "by_load"))
    assert ta.row_count == tb.row_count == len(rows["k"])
    assert ta.ndv == tb.ndv
    assert ta.mcv == tb.mcv
    assert set(ta.histograms) == set(tb.histograms) == {"k", "g", "d", "m"}
    for c, (edges, null_frac) in ta.histograms.items():
        assert np.array_equal(edges, tb.histograms[c][0]), c
        assert null_frac == tb.histograms[c][1] == 0.0
        # and bit-equal to what the host computed over the fetched column
        want = np.percentile(rows[c], np.linspace(
            0, 100, table_stats.HIST_BUCKETS + 1))
        assert np.array_equal(edges, want), c
    assert tb.ndv == {"k": 3000, "g": 17, "d": 400,
                      "m": len(np.unique(rows["m"])), "s": 41}


def _strings(kind: str) -> np.ndarray:
    rng = np.random.default_rng(11)
    if kind == "pooled":          # the generator's: rows share a pool
        pool = np.array([f"s{i}" for i in range(300)], dtype=object)
        return pool[rng.integers(0, 300, 20_000)]
    if kind == "own_objects":     # equal strings at many addresses
        return np.array([f"v{i % 97}" for i in range(20_000)], dtype=object)
    if kind == "strided":
        return _strings("pooled")[::3]
    if kind == "one":
        return np.array(["a"], dtype=object)
    if kind == "empty":
        return np.array([], dtype=object)
    return np.array(["b", "a", "b"])            # a fixed-width dtype


@pytest.mark.parametrize("kind", ["pooled", "own_objects", "strided", "one",
                                  "empty", "unicode_dtype"])
def test_factorised_strings_are_np_uniques(kind):
    """One way for an array of Python strings, whether its rows share
    their objects or not: the codes and the sorted dictionary are what
    ``np.unique`` gives."""
    from oceanbase_tpu.vector.column import factorize_strings

    strings = _strings(kind)
    codes, values = factorize_strings(strings)
    want_values, want_codes = np.unique(strings, return_inverse=True)
    assert codes.dtype == np.int32
    assert list(values) == list(want_values)
    assert np.array_equal(codes, want_codes)


def test_a_null_among_the_strings_fails_the_encode_as_it_did():
    from oceanbase_tpu.vector.column import factorize_strings

    with pytest.raises(TypeError):
        factorize_strings(np.array(["b", None, "a"], dtype=object))


@pytest.mark.parametrize("n", [1, 63, 64, 65, 1000, 4097])
def test_percentile_edges_are_numpys(n):
    rng = np.random.default_rng(n)
    a = np.sort(rng.integers(-10**12, 10**12, n))
    lo, hi, gamma = table_stats.percentile_positions(n)
    got = table_stats.percentile_edges(a[lo], a[hi], gamma)
    want = np.percentile(a, np.linspace(0, 100,
                                        table_stats.HIST_BUCKETS + 1))
    assert np.array_equal(got, want)


def test_a_reopened_database_serves_the_loaded_rows(two_ways):
    """Durability unchanged: the segment and its slog record were written
    before ``load_numpy`` returned."""
    _db, s, root, rows = two_ways
    want = s.execute("select count(*), sum(m), min(s), max(d) "
                     "from by_load").rows()
    assert want[0][0] == len(rows["k"])
    seg_dir = os.path.join(root, "sys", "segments")
    if not os.path.isdir(seg_dir):
        seg_dir = next(os.path.join(d, "segments")
                       for d, sub, _f in os.walk(root) if "segments" in sub)
    assert any(f.startswith("by_load_") for f in os.listdir(seg_dir))
    # a second Database over the same root recovers from manifest + slog
    # (the first stays open: nothing it holds in memory is consulted)
    again = Database(root)
    try:
        s2 = again.session()
        assert s2.execute("select count(*), sum(m), min(s), max(d) "
                          "from by_load").rows() == want
        s2.close()
    finally:
        again.close()
