"""The deployment ``tpch_sf10_orders`` at a small size on the CPU: Q4, Q13
and Q18 as its cell sends them, through ``Session.execute`` on a database
loaded and tuned as the configuration says, against the benchmark's exact
references, the program's own NumPy copies and SQLite, under the plan the
statistics give and under one a flipped estimate gives; ``ob_query_timeout``
(one deadline with ``query_timeout_s``); the rules of the bind phase that
the deployment forced; and the series it added.
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
from oceanbase_tpu.bench import numpy_ref  # noqa: E402
from oceanbase_tpu.bench.oracle import (load_sqlite, rows_match,  # noqa: E402
                                        run_oracle)
from oceanbase_tpu.bench.tpch_queries import QUERIES  # noqa: E402
from oceanbase_tpu.datatypes import SqlType, date_to_days  # noqa: E402
from oceanbase_tpu.exec import plan as pp  # noqa: E402
from oceanbase_tpu.server import Database  # noqa: E402
from oceanbase_tpu.server import admission as qadmission  # noqa: E402
from oceanbase_tpu.server import metrics as qmetrics  # noqa: E402
from oceanbase_tpu.sql.parser import parse_sql  # noqa: E402

SCALE = 0.02
SEED = 4400000021
TABLES = ("lineitem", "orders", "customer")
STATEMENTS = ("tpch_q4_sf10", "tpch_q13_sf10", "tpch_q18_sf10")


def _counter(name: str, **labels) -> float:
    key = qmetrics.series_id(name, labels)
    for n, lbl, v in qmetrics.wire_snapshot()["counters"]:
        if qmetrics.series_id(n, lbl) == key:
            return float(v)
    return 0.0


def _statement(name: str) -> dict:
    return bspec.read_json(os.path.join(REPO, "benchmark", "statements",
                                        name + ".json"))


def _sql(name: str) -> tuple[str, dict]:
    st = _statement(name)
    params = btraffic.validation_params(st)
    return btraffic.render(st, params), params


@pytest.fixture(scope="module")
def dataset():
    ds = bspec.load_module("datasets", "tpch_pooled")
    tables, types = ds.generate(SCALE, SEED)
    return ds, tables, types


@pytest.fixture(scope="module")
def config():
    return bspec.read_json(os.path.join(REPO, "benchmark", "configs",
                                        "tpch_sf10_orders.json"))


def _sql_types(types: dict, arrays: dict) -> dict:
    return {c: SqlType.decimal(v[1], v[2]) if v[0] == "decimal"
            else SqlType.date() for c, v in types.items() if c in arrays}


@pytest.fixture(scope="module")
def loaded(tmp_path_factory, dataset, config):
    """The configuration's set-up as ``harness/adapter.py`` does it."""
    ds, tables, types = dataset
    db = Database(str(tmp_path_factory.mktemp("sf10orders") / "db"))
    s = db.session()
    for sql in config["system_settings"]:
        s.execute(sql)
    for t in TABLES:
        s.catalog.load_numpy(t, tables[t], primary_key=ds.PRIMARY_KEYS[t],
                             types=_sql_types(types, tables[t]))
    for t in TABLES:
        s.execute(f"analyze table {t}")
    for sql in config["session_settings"]:
        s.execute(sql)
    yield db, s
    s.close()
    db.close()


@pytest.fixture(scope="module")
def sqlite(dataset):
    _ds, tables, types = dataset
    return load_sqlite({t: tables[t] for t in TABLES},
                       {c: t for name in TABLES
                        for c, t in _sql_types(types, tables[name]).items()})


@pytest.fixture()
def flipped(loaded):
    """Statistics that say ``customer`` is the large table and ``lineitem``
    the small one: every join the cost model orients by rows flips."""
    _db, s = loaded
    big, small = (s.catalog.table_def(t) for t in ("lineitem", "customer"))
    was = big.row_count, small.row_count
    big.row_count, small.row_count = was[1], was[0]
    yield
    big.row_count, small.row_count = was


def _joins(plan):
    return [(type(n).__name__, getattr(n, "how", None),
             pp.referenced_tables(n.left), pp.referenced_tables(n.right))
            for n in pp._postorder(plan)
            if isinstance(n, (pp.HashJoin, pp.SemiJoinResidual))]


# -- the three statements ------------------------------------------------------

@pytest.mark.parametrize("name", STATEMENTS)
def test_a_statement_equals_its_exact_reference_and_sqlite(
        loaded, dataset, sqlite, name):
    _ds, tables, _types = dataset
    _db, s = loaded
    st = _statement(name)
    sql, params = _sql(name)
    assert st["reference"] == {"sqlite": False,
                               "exact": st["reference"]["exact"]}
    ref = bspec.load_module("references", st["reference"]["exact"])
    spilled = _counter("sql.work_area_decisions", kind="spill")
    res = s.execute(sql)
    assert _counter("sql.work_area_decisions", kind="spill") == spilled
    want = ref.answer(tables, params)
    assert ref.extract(list(res.names), res.arrays) == want
    assert want not in (None, {}, [])
    # SQLite has no column list on a derived table: Q13 in the other
    # spelling of the same statement
    text = QUERIES[13] if name == "tpch_q13_sf10" else sql
    ok, why = rows_match(res.rows(), run_oracle(sqlite, text),
                         ordered=name != "tpch_q18_sf10")
    assert ok, why


@pytest.mark.parametrize("name", STATEMENTS)
def test_a_flipped_estimate_gives_another_plan_and_the_same_answer(
        loaded, dataset, flipped, name):
    _ds, tables, _types = dataset
    _db, s = loaded
    sql, params = _sql(name)
    ref = bspec.load_module("references",
                            _statement(name)["reference"]["exact"])
    res = s.execute(sql)
    assert ref.extract(list(res.names), res.arrays) \
        == ref.answer(tables, params)


def test_the_flip_reorders_q18s_joins(loaded, flipped):
    _db, s = loaded
    sql, _ = _sql("tpch_q18_sf10")
    flipped_plan, _o, _e = s._plan_select(parse_sql(sql), None)
    big = s.catalog.table_def("lineitem")
    small = s.catalog.table_def("customer")
    big.row_count, small.row_count = small.row_count, big.row_count
    try:
        plan, _o, _e = s._plan_select(parse_sql(sql), None)
    finally:
        big.row_count, small.row_count = small.row_count, big.row_count
    assert _joins(plan) != _joins(flipped_plan)


def test_the_programs_copies_agree_with_the_references(dataset):
    _ds, tables, _types = dataset
    refs = {q: bspec.load_module("references", f"tpch_q{q}_exact")
            for q in (4, 13, 18)}
    assert numpy_ref.numpy_q4(
        tables, date_to_days("1993-07-01"), date_to_days("1993-10-01")) \
        == refs[4].answer(tables, {"DATE": "1993-07-01"})
    assert numpy_ref.numpy_q13(tables) == refs[13].answer(
        tables, {"WORD1": "special", "WORD2": "requests"})
    for quantity in (300, 250):
        full = numpy_ref.numpy_q18(tables, quantity)
        want = refs[18].answer(tables, {"QUANTITY": str(quantity)})
        assert full[:100] == want["rows"] and full
        assert refs[18].Top(full[:100]) == want
    assert len(numpy_ref.numpy_q18(tables, 250)) > 100    # the limit cuts


def test_q18s_latitude_is_ties_on_both_sort_keys_and_nothing_else():
    ref = bspec.load_module("references", "tpch_q18_exact")
    rows = [("c%d" % i, i, i, 9000, 5000 - i, 30100) for i in range(99)]
    tied = [("t%d" % i, 200 + i, 200 + i, 9100, 1000, 30200)
            for i in range(3)]
    want = {"rows": rows + tied[:1], "tied_at_cut": tied}
    assert ref.Top(rows + tied[:1]) == want
    assert ref.Top(rows + tied[2:]) == want           # another of the tied
    assert ref.Top(rows + tied[:1]) != {**want, "rows": rows}    # a row short
    assert ref.Top(rows[1:] + tied[:2]) != want       # a row off the cut lost
    assert ref.Top(rows + [("x", 1, 1, 9100, 1000, 30200)]) != want
    assert ref.Top(rows[::-1] + tied[:1]) != want     # out of order
    swapped = [rows[1], rows[0]] + rows[2:]
    assert ref.Top(swapped + tied[:1]) != want        # order is compared


# -- ob_query_timeout: upstream's name for the one deadline ---------------------

def test_ob_query_timeout_and_query_timeout_s_are_one_deadline(tmp_path):
    db = Database(str(tmp_path / "db"))
    try:
        s = db.session()
        cfg = s.tenant.config if s.tenant is not None else db.config
        default = float(cfg["query_timeout_s"])
        assert s._stmt_timeout_s() == default
        assert cfg["ob_query_timeout"] == round(default * 1_000_000)
        # global scope, either name, microseconds
        s.execute("set global ob_query_timeout = 36000000000")
        assert cfg["query_timeout_s"] == 36000.0
        assert s._stmt_timeout_s() == db.session()._stmt_timeout_s() \
            == 36000.0
        s.execute("set global query_timeout_s = 120")
        assert cfg["ob_query_timeout"] == 120_000_000
        # session scope wins, either name
        s.execute("set ob_query_timeout = 500000")
        assert s._stmt_timeout_s() == 0.5
        shown = dict(s.execute("show variables").rows())
        assert shown["ob_query_timeout"] == "500000"
        assert float(shown["query_timeout_s"]) == 0.5
        s.execute("set query_timeout_s = 7")
        assert dict(s.execute("show variables").rows())[
            "ob_query_timeout"] == "7000000"
        # no second parameter came with the name
        assert "ob_query_timeout" not in cfg.defs()
        # the deadline in force is a tag of the statement's span
        s.execute("select 1")
        (tags,) = [r[4] for r in s.execute("show trace").rows()
                   if r[0].strip() == "statement"]
        assert '"ob_query_timeout": 7000000' in tags
    finally:
        db.close()


def test_an_expired_ob_query_timeout_raises_what_query_timeout_s_raises(
        tmp_path):
    db = Database(str(tmp_path / "db"))
    try:
        s = db.session()
        s.execute("create table big (a int primary key, b int)")
        s.execute("insert into big values " + ", ".join(
            f"({i}, {i % 97})" for i in range(20000)))
        db.config.set("sql_work_area_rows", 512)   # spill: many checkpoints
        sql = "select sum(b), count(*) from big where b < 90"
        for setting in ("set ob_query_timeout = 50000",
                        "set query_timeout_s = 0.05"):
            s.execute(setting)
            with pytest.raises(qadmission.QueryTimeout):
                s.execute(sql)
        s.execute("set ob_query_timeout = 36000000000")
        assert s.execute("select count(*) from big").rows() == [(20000,)]
    finally:
        db.close()


# -- what the deployment forced in the bind phase ---------------------------------

def _nodes(plan, kind):
    return [n for n in pp._postorder(plan) if isinstance(n, kind)]


def test_q18s_having_is_priced_and_its_inputs_are_compacted(loaded):
    """``sum(l_quantity) > 300`` by Cantelli's bound from ANALYZE's
    histogram of ``l_quantity`` and the rows a group (well under the flat
    third), the subquery's group-by sized by ``l_orderkey``'s distinct
    values, what the HAVING and the semi-join leave compacted to their
    estimates' buckets, and ``customer`` joined on the probe's lanes."""
    _db, s = loaded
    sql, _ = _sql("tpch_q18_sf10")
    plan, _o, _e = s._plan_select(parse_sql(sql), None)
    orders = s.catalog.table_def("orders").row_count
    inner = [g for g in _nodes(plan, pp.GroupBy) if len(g.keys) == 1][0]
    assert orders <= inner.out_capacity < 4 * orders
    (having,) = [f for f in _nodes(plan, pp.Filter) if f.child is inner]
    assert having.est_rows < inner.est_rows // 8
    compacts = _nodes(plan, pp.Compact)
    assert len(compacts) == 2 and all(c.strict for c in compacts)
    build, probe = compacts
    assert isinstance(build.child, pp.Project) \
        and build.child.child is having
    assert isinstance(probe.child, pp.HashJoin) \
        and probe.child.how == "semi" and probe.child.right is build
    assert probe.capacity * 8 <= s.catalog.scan_lanes("orders")
    text = "\n".join(r[0] for r in s.execute("explain " + sql).rows())
    assert text.count("Compact(capacity=") == 2
    assert "HashJoin(unique build, on probe lanes" in text


def test_an_outer_join_is_sized_by_its_matches(loaded):
    """An outer join that expands emits one lane a MATCH (ten orders a
    customer), not one a customer: Q13 with ``count(*)``, which counts the
    NULL-extended row and so keeps its group-by above the join.  Q13
    itself groups ``orders`` below the join (PR 46) and joins on
    ``customer``'s lanes; either way the ON predicate over ``o_comment``
    is priced from ANALYZE's sample (99 % pass), not at a third."""
    _db, s = loaded
    sql, _ = _sql("tpch_q13_sf10")
    orders = s.catalog.table_def("orders").row_count
    plan, _o, _e = s._plan_select(
        parse_sql(sql.replace("count(o_orderkey)", "count(*)")), None)
    (join,) = _nodes(plan, pp.HashJoin)
    assert join.how == "left" and not join.build_unique
    assert join.out_capacity >= orders
    assert isinstance(join.right, pp.Filter)
    assert join.right.est_rows > orders * 0.9
    assert not any(g.below_join for g in _nodes(plan, pp.GroupBy))

    plan, _o, _e = s._plan_select(parse_sql(sql), None)
    (join,) = _nodes(plan, pp.HashJoin)
    below = join.right
    assert join.how == "left" and join.build_unique
    assert isinstance(below, pp.GroupBy) and below.below_join
    assert isinstance(below.child, pp.Filter)
    assert below.child.est_rows > orders * 0.9
    # every preserved row once, on the preserved side's lanes
    customers = s.catalog.table_def("customer").row_count
    assert customers <= join.out_capacity < orders
    before = _counter("plan.capacity_retries")
    s.execute(sql)
    assert _counter("plan.capacity_retries") == before


def test_a_derived_tables_column_list_names_its_columns(loaded):
    _db, s = loaded
    rows = s.execute(
        "select k, n from (select o_orderpriority, count(*) from orders "
        "group by o_orderpriority) as t (k, n) order by k").rows()
    assert len(rows) == 5 and sum(n for _k, n in rows) == \
        s.catalog.table_def("orders").row_count
    with pytest.raises(Exception, match="declares 1 columns"):
        s.execute("select * from (select o_orderkey, o_custkey from "
                  "orders) as t (k)")


# -- the series the deployment added ---------------------------------------------

def test_joins_are_counted_by_kind_and_sorted_group_bys_by_lanes(loaded):
    _db, s = loaded
    kinds = ("inner", "left", "semi", "anti", "full")

    def read():
        return ({k: _counter("plan.join_kinds", how=k) for k in kinds},
                _counter("plan.groupby_sort_lanes"),
                _counter("plan.groupby_out_lanes"),
                _counter("plan.groupby_groups"))

    orders = s.catalog.table_def("orders").row_count
    customers = s.catalog.table_def("customer").row_count
    deltas = {}
    for name in STATEMENTS:
        sql, _ = _sql(name)
        s.execute(sql)                      # compiled and noted
        k0, sort0, out0, groups0 = read()
        res = s.execute(sql)
        k1, sort1, out1, groups1 = read()
        deltas[name] = ({k: k1[k] - k0[k] for k in kinds if k1[k] != k0[k]},
                        sort1 - sort0, out1 - out0, groups1 - groups0,
                        res.rowcount)
        if name == "tpch_q13_sf10":
            res13 = res.rows()              # (c_count, custdist)
    # Q4: one semi-join (the exact-key path that only masks its probe is
    # counted too), its group-by is masked: no sorted lanes
    assert deltas["tpch_q4_sf10"][:4] == ({"semi": 1}, 0, 0, 0)
    # Q13: one left join; three sort-path group-bys: orders by customer
    # below the join (over orders' lanes), the partial counts combined
    # by customer above it (over customer's), then the distinct counts;
    # the groups found are the customers with a counted order, every
    # customer, and the distinct counts
    kinds13, sort13, out13, groups13, rows13 = deltas["tpch_q13_sf10"]
    assert kinds13 == {"left": 1}
    assert sort13 == s.catalog.scan_lanes("orders") \
        + 2 * s.catalog.scan_lanes("customer")
    without_one = next(n for c, n in res13 if c == 0)
    assert groups13 == (customers - without_one) + customers + rows13
    assert out13 >= groups13
    # Q18: a semi-join and two inner joins; the subquery's group-by sorts
    # the whole of lineitem's lanes and finds every order
    kinds18, sort18, out18, groups18, rows18 = deltas["tpch_q18_sf10"]
    assert kinds18 == {"semi": 1, "inner": 2}
    assert sort18 >= s.catalog.scan_lanes("lineitem")
    assert groups18 == orders + rows18 and out18 >= groups18
