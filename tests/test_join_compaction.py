"""A join's lanes follow what its inputs' range filters leave: the planner
puts a strict ``Compact`` at the estimate's bucket under a join whose input
is estimated to fill at most 1/8 of the lanes it arrives on
(``sql/optimizer.py::compact_join_input``), a row that does not fit is
never dropped (``compact_overflow`` -> ``CapacityOverflow`` -> the session's
ladder), and every execution books its join inputs by kind
(``plan.join_inputs{kind=compacted|whole}``), serial and PX.

The PX cases run on four of the eight virtual CPU devices ``conftest.py``
forces, over tables hash-partitioned by DDL as ``tests/test_hash_partition.py``
creates them.
"""

import jax
import numpy as np
import pytest

from oceanbase_tpu.bench.oracle import load_sqlite, rows_match, run_oracle
from oceanbase_tpu.bench.tpch import gen_tpch
from oceanbase_tpu.bench.tpch_queries import QUERIES
from oceanbase_tpu.exec import plan as pp
from oceanbase_tpu.exec.diag import CapacityOverflow
from oceanbase_tpu.server import Database
from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.sql.optimizer import _bucket, apply_feedback
from oceanbase_tpu.sql.parser import parse_sql
from test_hash_partition import TABLES, _ddl, _load

needs_four = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 devices")

# exec/plan.py::logical_hash of the plans the parent commit (PR 31) binds
# for these statements over this data with ANALYZE'd statistics, serial
# and at px_dop 4 over the partitioned tables alike
PARENT_LOGICAL_HASH = {1: "0ca9da09f2dcf53f", 3: "dceb0bfea97c64e0",
                       6: "17ee19f6c9c673b0"}


@pytest.fixture(scope="module")
def data():
    tables, types = gen_tpch(sf=0.01)
    return {t: tables[t] for t in TABLES}, types


def _boot(data, root, partitioned: bool):
    tables, types = data
    db = Database(str(root))
    s = db.session()
    if partitioned:
        s.execute("create tablegroup tg_orders")
        for name, arrays in tables.items():
            s.execute(_ddl(name, arrays, types))
    _load(s, tables, types)
    for name in tables:
        s.execute(f"analyze table {name}")
    return db, s


@pytest.fixture(scope="module")
def serial(data, tmp_path_factory):
    db, s = _boot(data, tmp_path_factory.mktemp("serial"), False)
    yield s
    db.close()


@pytest.fixture(scope="module")
def px4(data, tmp_path_factory):
    db, s = _boot(data, tmp_path_factory.mktemp("px4"), True)
    s.execute("set px_dop = 4")
    yield s
    db.close()


@pytest.fixture(scope="module")
def sqlite(data):
    return load_sqlite(*data)


def _nodes(plan, kind):
    return [n for n in pp._postorder(plan) if isinstance(n, kind)]


def _join_input_counts():
    return {k: qmetrics.counter_value("plan.join_inputs", kind=k)
            for k in ("compacted", "whole")}


# -- the plan ---------------------------------------------------------------

def test_q14_holds_a_strict_compact_at_the_estimates_bucket(serial):
    plan, _outs, _est = serial._plan_select(parse_sql(QUERIES[14]), None)
    (join,) = _nodes(plan, pp.HashJoin)
    (compact,) = _nodes(plan, pp.Compact)
    # directly under the join, over the two range filters and the scan
    assert compact in (join.left, join.right)
    assert compact.strict
    chain = compact.child
    assert isinstance(chain, pp.Filter) \
        and isinstance(chain.child, pp.Filter) \
        and isinstance(chain.child.child, pp.TableScan) \
        and chain.child.child.table == "lineitem"
    # the estimate's bucket, at the slack every capacity gets
    assert compact.capacity == _bucket(chain.est_rows, 1.5)
    lanes = serial.catalog.scan_lanes("lineitem")
    assert lanes == serial.catalog.table_data("lineitem").capacity
    assert compact.capacity * 8 <= lanes
    # ... and the join is priced on rows that its lanes now match: its
    # budget follows the interval, not 0.47 x 0.54 of lineitem
    assert join.out_capacity <= compact.capacity


@pytest.mark.parametrize("path", ["serial", pytest.param(
    "px4", marks=needs_four)])
@pytest.mark.parametrize("qnum", [1, 3, 6])
def test_statements_without_a_narrow_join_input_keep_their_plan(
        qnum, path, request, sqlite):
    """Q1 and Q6 have no join, Q3's filters are one-sided and leave 20-53 %
    of their lanes: no Compact, the parent's logical plan."""
    s = request.getfixturevalue(path)
    plan, _outs, _est = s._plan_select(parse_sql(QUERIES[qnum]), None)
    assert not _nodes(plan, pp.Compact)
    assert pp.logical_hash(plan) == PARENT_LOGICAL_HASH[qnum]
    before = _join_input_counts()
    rows = s.execute(QUERIES[qnum]).rows()
    after = _join_input_counts()
    assert bool(s._last_px) == (path == "px4")
    ok, why = rows_match(rows, run_oracle(sqlite, QUERIES[qnum]),
                         ordered=True)
    assert ok, why
    assert after["compacted"] == before["compacted"]
    # Q3's two joins take four whole inputs
    assert after["whole"] - before["whole"] == (4 if qnum == 3 else 0)


# -- the counter --------------------------------------------------------------

@pytest.mark.parametrize("path", ["serial", pytest.param(
    "px4", marks=needs_four)])
def test_join_inputs_are_booked_per_execution(path, request, sqlite):
    """Q14's one join: the filtered lineitem compacted, part whole; the
    executable keeps the counts and every execution adds them."""
    s = request.getfixturevalue(path)
    want = run_oracle(sqlite, QUERIES[14])
    for _ in range(3):
        before = _join_input_counts()
        rows = s.execute(QUERIES[14]).rows()
        after = _join_input_counts()
        assert bool(s._last_px) == (path == "px4")
        assert after["compacted"] - before["compacted"] == 1
        assert after["whole"] - before["whole"] == 1
        ok, why = rows_match(rows, want, ordered=True)
        assert ok, why


# -- safety: a row that does not fit is never dropped -----------------------

N = 40_000
SQL = ("select count(*), sum(bv), min(av), max(av) from ca, cb "
       "where aj = bk and av >= 1000 and av < 1400")


def _load_pair(s):
    rng = np.random.default_rng(32)
    s.catalog.load_numpy(
        "ca", {"ak": np.arange(N), "aj": rng.integers(0, 500, N),
               "av": rng.integers(0, 4000, N)}, primary_key=["ak"])
    s.catalog.load_numpy(
        "cb", {"bk": np.arange(500), "bv": rng.integers(0, 100, 500)},
        primary_key=["bk"])


def test_an_overflowing_compact_replans_to_the_uncompacted_answer(
        tmp_path, new_session):
    """Statistics gone stale: the histogram says the interval is almost
    empty, a tenth of the rows lie in it.  The bucket is too small, the
    program reports what did not fit, the session re-plans with scaled
    budgets, and the answer is the uncompacted plan's, row for row."""
    from oceanbase_tpu.exec.plan import execute_plan

    plain = new_session()
    _load_pair(plain)  # no ANALYZE: 0.4 x 0.4 of the lanes, no Compact
    plan, _o, _e = plain._plan_select(parse_sql(SQL), None)
    assert not _nodes(plan, pp.Compact)
    want = plain.execute(SQL).rows()
    assert want[0][0] > N // 12

    db = Database(str(tmp_path / "db"))
    s = db.session()
    _load_pair(s)
    s.execute("analyze table ca")
    s.execute("analyze table cb")
    td = s.catalog.table_def("ca")
    edges, null_frac = td.histograms["av"]
    stale = np.sort(np.where((edges >= 1000) & (edges < 1400), 990.0,
                             edges))
    td.histograms["av"] = (stale, null_frac)
    plan, _o, _e = s._plan_select(parse_sql(SQL), None)
    (compact,) = _nodes(plan, pp.Compact)
    assert compact.strict and compact.capacity < want[0][0]
    tables = {t: s.catalog.table_data(t) for t in ("ca", "cb")}
    with pytest.raises(CapacityOverflow) as err:
        execute_plan(plan, tables)
    (lane, cap, dropped), = [d for d in err.value.drops
                             if d[0] == "compact_overflow"]
    assert cap == compact.capacity and dropped > 0

    retries = qmetrics.counter_value("plan.capacity_retries")
    assert s.execute(SQL).rows() == want
    assert qmetrics.counter_value("plan.capacity_retries") > retries
    # the scaled plan took the cached one's place: no second ladder
    retries = qmetrics.counter_value("plan.capacity_retries")
    assert s.execute(SQL).rows() == want
    assert qmetrics.counter_value("plan.capacity_retries") == retries
    db.close()


def test_feedback_raises_a_compact_with_the_filter_under_it(new_session):
    """gv$plan_feedback's correction for the filter chain's head (the
    Compact is a pass-through with no ledger row) raises the Compact's
    bucket at bind time; it never lowers one."""
    s = new_session()
    _load_pair(s)
    plan = pp.propagate_estimates(pp.HashJoin(
        pp.Compact(pp.Filter(pp.TableScan("ca", est_rows=N),
                             parse_sql("select 1 from ca where av < 9")
                             .where, est_rows=40),
                   capacity=128, strict=True, est_rows=40),
        pp.TableScan("cb", est_rows=500), [], [], out_capacity=4096))
    order = pp.monitored_postorder(plan)
    pos = [type(n).__name__ for n in order].index("Filter")
    raised, n = apply_feedback(plan, {pos: ("Filter", 5000)})
    (compact,) = _nodes(raised, pp.Compact)
    assert n == 1 and compact.capacity == _bucket(5000, 1.5)
    same, n = apply_feedback(plan, {pos: ("Filter", 50)})
    assert n == 0 and _nodes(same, pp.Compact)[0].capacity == 128
