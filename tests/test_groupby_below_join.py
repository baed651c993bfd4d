"""A group-by over an outer join's NULL-supplying side runs below the join.

Where a ``GROUP BY`` sits directly on a LEFT OUTER JOIN, groups by columns
of the preserved side and aggregates columns of the NULL-supplying side
alone (``count(x)``, ``sum(x)``, ``min(x)``, ``max(x)``), the binder plans
the aggregation under the join (``sql/binder.py::_groupby_below_join``):
the NULL-supplying side grouped by its join key, the same left join against
that group-by on the preserved side's lanes, the partials combined by the
statement's own keys above it, an unmatched group's count read as 0.  The
rule has no switch: "the unrewritten plan" below is the binder with the one
method patched to leave the plan as it found it.

Each statement runs both ways and in SQLite; ``EXPLAIN`` and
``plan.groupby_placements{at=below_join|above_join}`` say which way it went.
"""

import contextlib
import sqlite3

import jax
import numpy as np
import pytest

from oceanbase_tpu.bench.oracle import rows_match, run_oracle
from oceanbase_tpu.exec import plan as pp
from oceanbase_tpu.server import Database
from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.sql import binder as qbinder
from oceanbase_tpu.sql.parser import parse_sql

needs_four = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 devices")

DDL = {
    "c": "create table c (ck int primary key, grp int, nm varchar(8))",
    # no declared key: ck repeats and is NULL on some rows
    "cd": "create table cd (id int primary key, ck int, grp int)",
    "o": "create table o (ok int primary key, ck int, ck2 int, x int, "
         "amt decimal(12,2), note varchar(8))",
    "tiny": "create table tiny (ck int primary key, grp int)",
}


def _rows(seed: int = 46) -> dict:
    r = np.random.default_rng(seed)

    def maybe(v, p):
        return None if r.random() < p else v

    rows = {
        # customers 0..199; orders name 0..99, 120..259 and 200..219: some
        # customers have no order and some orders no customer
        "c": [(k, maybe(k % 7, 0.1), "n%d" % (k % 13)) for k in range(200)],
        "cd": [(i, maybe(int(r.integers(0, 150)), 0.1), int(i % 5))
               for i in range(300)],
        "tiny": [(k, k % 3) for k in range(0, 200, 20)],
    }
    orders = []
    for i in range(3000):
        ck = maybe(int(r.integers(0, 260)), 0.05)
        if ck is not None and 100 <= ck < 120:
            ck += 100
        # customers 40..49 match, and every x of theirs is NULL: count 0
        # through a match, sum / min / max NULL
        x = None if ck is not None and 40 <= ck < 50 else \
            maybe(int(r.integers(-50, 1000)), 0.2)
        orders.append((i, ck, None if ck is None else ck % 7, x,
                       maybe(round(float(r.integers(0, 100000)) / 100, 2),
                             0.1),
                       ("keep", "drop", "hold")[int(r.integers(0, 3))]))
    rows["o"] = orders
    return rows


def _values(rows) -> str:
    def lit(v):
        if v is None:
            return "null"
        return "'%s'" % v if isinstance(v, str) else repr(v)

    return ", ".join("(" + ", ".join(lit(v) for v in row) + ")"
                     for row in rows)


@pytest.fixture(scope="module")
def loaded(tmp_path_factory):
    db = Database(str(tmp_path_factory.mktemp("gbj") / "db"))
    s = db.session()
    lite = sqlite3.connect(":memory:")
    for name, rows in _rows().items():
        s.execute(DDL[name])
        lite.execute(DDL[name])
        s.execute(f"insert into {name} values {_values(rows)}")
        lite.execute(f"insert into {name} values {_values(rows)}")
        s.execute(f"analyze table {name}")
    yield s, lite
    s.close()
    db.close()


def _placements() -> dict:
    return {at: qmetrics.counter_value("plan.groupby_placements", at=at)
            for at in ("below_join", "above_join")}


def _moved(before: dict) -> dict:
    return {at: n - before[at] for at, n in _placements().items()
            if n != before[at]}


def _plan(s, sql):
    return s._plan_select(parse_sql(sql), None)[0]


def _explain(s, sql) -> str:
    return "\n".join(r[0] for r in s.execute("explain " + sql).rows())


@contextlib.contextmanager
def _unrewritten(s):
    """The binder with the rule's one method leaving every plan as it
    found it (and no plan of the other binder in the session's cache)."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(qbinder.Binder, "_groupby_below_join",
                  lambda self, g, qb, agg_calls: g)
        s.plan_cache.clear()
        yield
    s.plan_cache.clear()


def _check_pushed(s, lite, sql):
    """``sql`` takes the rule: its plan, its answer against SQLite and
    against the plan the rule leaves alone, and its note."""
    plan = _plan(s, sql)
    (join,) = [n for n in pp._postorder(plan) if isinstance(n, pp.HashJoin)]
    below = join.right
    assert join.how == "left" and isinstance(below, pp.GroupBy) \
        and below.below_join
    assert [k.name for k in join.right_keys] == list(below.keys)
    assert "GroupBy(below join, " in _explain(s, sql)
    assert "below_join=True" in plan.fingerprint()
    before = _placements()
    got = s.execute(sql).rows()
    assert _moved(before) == {"below_join": 1}
    ok, why = rows_match(got, run_oracle(lite, sql), ordered=True)
    assert ok, why
    with _unrewritten(s):
        assert not any(g.below_join for g in pp._postorder(_plan(s, sql))
                       if isinstance(g, pp.GroupBy))
        before = _placements()
        want = s.execute(sql).rows()
        assert _moved(before) == {"above_join": 1}
    assert got == want
    return plan, got


# -- the rule engages ------------------------------------------------------------

@pytest.mark.parametrize("agg", ["count(x)", "sum(x)", "min(x)", "max(x)",
                                 "sum(amt)", "min(note)", "sum(x + 1)",
                                 "count(x), sum(x), min(amt), max(ok)"])
def test_an_aggregate_over_a_nullable_argument(loaded, agg):
    s, lite = loaded
    sql = (f"select c.ck, {agg} from c left join o on c.ck = o.ck "
           "group by c.ck order by c.ck")
    _plan_, got = _check_pushed(s, lite, sql)
    assert len(got) == 200


def test_customers_without_a_match_count_zero_and_sum_null(loaded):
    s, lite = loaded
    sql = ("select c.ck, count(x), sum(x), count(o.ok) from c left join o "
           "on c.ck = o.ck group by c.ck order by c.ck")
    plan, got = _check_pushed(s, lite, sql)
    by_key = {k: rest for k, *rest in got}
    unmatched = [k for k in range(200) if by_key[k][2] == 0]
    assert unmatched and all(by_key[k] == [0, None, 0] for k in unmatched)
    # matched, and every x NULL: a count of 0 beside a count of orders
    assert all(by_key[k][:2] == [0, None] and by_key[k][2] > 0
               for k in range(40, 50))
    # count is a NOT NULL BIGINT after the rewrite as before it
    res = s.execute(sql)
    assert all(v is not None for row in res.rows() for v in (row[1], row[3]))


def test_null_join_keys_on_both_sides_match_nothing(loaded):
    s, lite = loaded
    sql = ("select cd.id, count(o.x), max(o.x) from cd left join o "
           "on cd.ck = o.ck group by cd.id order by cd.id")
    _plan_, got = _check_pushed(s, lite, sql)
    null_keys = {i for i, ck, _g in _rows()["cd"] if ck is None}
    assert null_keys and all(row[1:] == (0, None) for row in got
                             if row[0] in null_keys)


def test_a_preserved_side_with_duplicate_keys(loaded):
    """``cd.ck`` repeats: each of its rows pairs with every order of the
    key, so a key's orders count once a row.  The group-by above the join
    is why the answer holds."""
    s, lite = loaded
    sql = ("select cd.ck, count(o.x), sum(o.x), min(o.amt) from cd "
           "left join o on cd.ck = o.ck group by cd.ck order by cd.ck")
    plan, got = _check_pushed(s, lite, sql)
    keys = [ck for _i, ck, _g in _rows()["cd"]]
    assert len(got) == len(set(keys)) < len(keys)
    upper = [g for g in pp._postorder(plan) if isinstance(g, pp.GroupBy)
             and not g.below_join]
    assert [a.fn for g in upper for a in g.aggs] == ["sum", "sum", "min"]


def test_an_on_filter_on_the_null_supplying_side_stays_under_it(loaded):
    s, lite = loaded
    sql = ("select c.ck, count(o.ok), sum(o.x) from c left join o "
           "on c.ck = o.ck and o.note <> 'drop' and o.ok >= 100 "
           "group by c.ck order by c.ck")
    plan, _got = _check_pushed(s, lite, sql)
    (below,) = [g for g in pp._postorder(plan) if isinstance(g, pp.GroupBy)
                and g.below_join]
    assert isinstance(below.child, pp.Filter) \
        and isinstance(below.child.child, pp.Filter)


@pytest.mark.parametrize("keys", ["c.grp", "c.grp, c.nm", "c.nm, c.ck"])
def test_a_group_key_that_is_not_the_join_key(loaded, keys):
    s, lite = loaded
    sql = (f"select {keys}, count(o.x), sum(o.amt), max(o.x) from c "
           f"left join o on c.ck = o.ck group by {keys} order by {keys}")
    _check_pushed(s, lite, sql)


def test_a_having_and_a_group_by_above_read_the_pushed_aggregates(loaded):
    """Q13's shape: the counts keyed on by a second group-by, and a HAVING
    over a pushed aggregate."""
    s, lite = loaded
    q13 = ("select n, count(*) from (select c.ck, count(o.ok) from c "
           "left join o on c.ck = o.ck and o.note <> 'hold' group by c.ck) "
           "as t (ck, n) group by n order by n")
    oracle = ("select n, count(*) from (select c.ck as ck, count(o.ok) as n "
              "from c left join o on c.ck = o.ck and o.note <> 'hold' "
              "group by c.ck) as t group by n order by n")
    before = _placements()
    got = s.execute(q13).rows()
    assert _moved(before) == {"below_join": 1}
    assert got == run_oracle(lite, oracle) and got[0][0] == 0
    having = ("select c.ck, sum(o.x) from c left join o on c.ck = o.ck "
              "group by c.ck having count(o.x) > 9 and sum(o.x) > 5000 "
              "order by c.ck")
    _plan_, got = _check_pushed(s, lite, having)
    assert 0 < len(got) < 200


# -- the refusals: today's plan, today's answer -----------------------------------

REFUSED = {
    "count_star": "select c.ck, count(*) from c left join o on c.ck = o.ck "
                  "group by c.ck order by c.ck",
    "count_literal": "select c.ck, count(1) from c left join o "
                     "on c.ck = o.ck group by c.ck order by c.ck",
    "preserved_side_argument":
        "select c.ck, count(o.x), sum(c.grp) from c left join o "
        "on c.ck = o.ck group by c.ck order by c.ck",
    "not_null_on_extension":
        "select c.ck, sum(coalesce(o.x, 1)) from c left join o "
        "on c.ck = o.ck group by c.ck order by c.ck",
    "distinct": "select c.ck, count(distinct o.x) from c left join o "
                "on c.ck = o.ck group by c.ck order by c.ck",
    "sum_distinct": "select c.ck, count(o.x), sum(distinct o.ck2) from c "
                    "left join o on c.ck = o.ck group by c.ck order by c.ck",
    "avg": "select c.ck, avg(o.x) from c left join o on c.ck = o.ck "
           "group by c.ck order by c.ck",
    "null_side_key": "select o.ck2, count(o.x) from c left join o "
                     "on c.ck = o.ck group by o.ck2 order by o.ck2",
    "where": "select c.ck, count(o.x) from c left join o on c.ck = o.ck "
             "where o.x is null or o.x > 5 group by c.ck order by c.ck",
    "left_side_on_predicate":
        "select c.ck, count(o.x) from c left join o on c.ck = o.ck "
        "and c.grp > 2 group by c.ck order by c.ck",
    "full_join": "select c.ck, count(o.x) from c full join o "
                 "on c.ck = o.ck group by c.ck order by c.ck",
    "two_key_pairs": "select c.ck, count(o.x) from c left join o "
                     "on c.ck = o.ck and c.grp = o.ck2 group by c.ck "
                     "order by c.ck",
    "no_group_key": "select count(o.x), sum(o.x) from c left join o "
                    "on c.ck = o.ck",
    # rule 2: ten rows join into 256 lanes; grouping the whole of o costs
    # more than the join saves
    "tiny_preserved_side":
        "select tiny.ck, count(o.x), sum(o.x) from tiny left join o "
        "on tiny.ck = o.ck group by tiny.ck order by tiny.ck",
}
#: compared to the program alone, where today's plan and SQLite differ by
#: older faults that are not this rule's (the test holds the plan to be
#: today's): sum(distinct) is bound as sum, count(distinct) loses a 0 that
#: lies beside a NULL, a left-side ON predicate is applied as a filter above the join.  SQLite
#: 3.39 brought FULL JOIN
NOT_IN_SQLITE = {"sum_distinct", "distinct", "left_side_on_predicate"} | (
    set() if sqlite3.sqlite_version_info >= (3, 39) else {"full_join"})
#: where a Filter lies between, or the join is no left join, the group-by
#: is not "directly over a left join": no note either way
NOT_NOTED = {"where", "left_side_on_predicate", "full_join", "no_group_key"}


@pytest.mark.parametrize("why", sorted(REFUSED))
def test_a_refusal_keeps_todays_plan_and_answer(loaded, why):
    s, lite = loaded
    sql = REFUSED[why]
    plan = _plan(s, sql)
    with _unrewritten(s):
        assert pp.logical_hash(_plan(s, sql)) == pp.logical_hash(plan)
    assert not any(g.below_join for g in pp._postorder(plan)
                   if isinstance(g, pp.GroupBy))
    assert "below join" not in _explain(s, sql)
    assert "below_join" not in plan.fingerprint()
    before = _placements()
    got = s.execute(sql).rows()
    assert _moved(before) == ({} if why in NOT_NOTED else {"above_join": 1})
    if why not in NOT_IN_SQLITE:
        ok, msg = rows_match(got, run_oracle(lite, sql), ordered=True)
        assert ok, msg


def test_rule_two_compares_the_joins_lanes_with_the_null_supplying_sides(
        loaded):
    """The same statement is pushed over ``c`` (200 customers join into at
    least ``o``'s lanes) and refused over ``tiny`` (ten join into 256)."""
    s, _lite = loaded
    lanes = s.catalog.scan_lanes("o")
    with _unrewritten(s):
        joins = {t: next(n for n in pp._postorder(_plan(
            s, f"select {t}.ck, count(o.x) from {t} left join o "
               f"on {t}.ck = o.ck group by {t}.ck"))
            if isinstance(n, pp.HashJoin)) for t in ("c", "tiny")}
    assert joins["tiny"].out_capacity < lanes <= joins["c"].out_capacity


def test_the_pushed_join_is_unique_by_construction_not_by_proof(loaded):
    """``unique_build``'s proof rules do not know a group-by; the rewrite
    marks its own join, and the marked join reports no repeated key."""
    from oceanbase_tpu.sql.optimizer import unique_build

    s, _lite = loaded
    sql = ("select cd.ck, count(o.x) from cd left join o on cd.ck = o.ck "
           "group by cd.ck")
    (join,) = [n for n in pp._postorder(_plan(s, sql))
               if isinstance(n, pp.HashJoin)]
    assert join.build_unique
    assert not unique_build(join.left, join.right, join.right_keys,
                            join.out_capacity, s.catalog)
    retries = qmetrics.counter_value("plan.capacity_retries")
    s.execute(sql)
    assert qmetrics.counter_value("plan.capacity_retries") == retries


def test_a_pushed_plan_travels_through_dtl_with_its_mark(loaded):
    from oceanbase_tpu.px import dtl

    s, _lite = loaded
    plan = _plan(s, "select c.ck, count(o.x) from c left join o "
                    "on c.ck = o.ck group by c.ck")
    (below,) = [g for g in pp._postorder(plan) if isinstance(g, pp.GroupBy)
                and g.below_join]
    back = dtl.decode_plan(dtl.encode_plan(below))
    assert back.below_join and back.fingerprint() == below.fingerprint()


@needs_four
def test_a_px_plan_lowers_the_same_nodes(loaded):
    """At ``px_dop = 4`` the PX planner lowers the nodes it already knows
    (a group-by, a left join marked unique, a group-by) and books the
    pushed group-by once a statement."""
    s, lite = loaded
    sql = ("select c.grp, count(o.x), sum(o.amt), min(o.x) from c "
           "left join o on c.ck = o.ck group by c.grp order by c.grp")
    assert any(g.below_join for g in pp._postorder(_plan(s, sql))
               if isinstance(g, pp.GroupBy))
    s.execute("set px_dop = 4")
    try:
        before = _placements()
        got = s.execute(sql).rows()
        assert s._last_px
        assert _moved(before) == {"below_join": 1}
    finally:
        s.execute("set px_dop = 1")
    ok, why = rows_match(got, run_oracle(lite, sql), ordered=True)
    assert ok, why


@pytest.mark.parametrize("order_by", ["count(o.x) desc, c.ck",
                                      "sum(o.amt), c.ck", "2 desc, 1"])
def test_an_aggregate_that_order_by_alone_names_is_pushed_with_the_rest(
        loaded, order_by):
    s, lite = loaded
    sql = ("select c.ck, sum(o.x) from c left join o on c.ck = o.ck "
           f"group by c.ck order by {order_by}")
    plan, _got = _check_pushed(s, lite, sql)
    (below,) = [g for g in pp._postorder(plan) if isinstance(g, pp.GroupBy)
                and g.below_join]
    assert len(below.aggs) == (1 if order_by[0] == "2" else 2)
