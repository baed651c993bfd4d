"""A join whose build side is unique on the key emits on its probe's lanes.

The planner marks a join (``HashJoin.build_unique``) when one side is a
filter chain over a scan and the one join key that table's declared
single-column primary key, and the other side's static lanes fit the
``out_capacity`` the join would expand into
(``sql/optimizer.py::unique_build``); ``exec/ops.py::join`` then pairs each
probe lane with at most one build row and leaves the probe's columns where
they are.  The guarantee is checked at run time: a build side that repeats
a key is counted on the ``join_build_dup`` lane and the statement re-plans
with the mark off.  Every execution books its joins by emit kind
(``plan.join_emits{kind=probe_lanes|expanded}``), serial and PX.

The PX cases run on four of the eight virtual CPU devices ``conftest.py``
forces, over tables hash-partitioned by DDL as ``tests/test_hash_partition.py``
creates them.
"""

import sqlite3

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oceanbase_tpu.bench.oracle import load_sqlite, rows_match, run_oracle
from oceanbase_tpu.bench.tpch import gen_tpch
from oceanbase_tpu.bench.tpch_queries import QUERIES
from oceanbase_tpu.exec import diag, ops
from oceanbase_tpu.exec import plan as pp
from oceanbase_tpu.exec.diag import CapacityOverflow
from oceanbase_tpu.expr import ir
from oceanbase_tpu.px import dtl
from oceanbase_tpu.server import Database
from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.sql.optimizer import after_overflow, without_unique_builds
from oceanbase_tpu.sql.parser import parse_sql
from oceanbase_tpu.vector.column import Relation, from_numpy, to_numpy
from test_hash_partition import TABLES, _ddl, _load
from test_join_compaction import PARENT_LOGICAL_HASH

needs_four = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 devices")


def _joins(plan):
    return [n for n in pp._postorder(plan) if isinstance(n, pp.HashJoin)]


def _emits():
    return {k: qmetrics.counter_value("plan.join_emits", kind=k)
            for k in ("probe_lanes", "expanded")}


# -- (1) the operator: both paths give the same live rows -------------------

def _sides(ln: int, rn: int, seed: int):
    """A probe with repeated keys, keys the build lacks, NULL keys and dead
    lanes; a build side whose LIVE rows with a key hold it once (its dead
    and NULL-key lanes repeat live keys on purpose)."""
    r = np.random.default_rng(seed)
    bk = r.permutation(3 * rn)[:rn].astype(np.int64) - rn
    b_live = r.random(rn) < 0.8
    b_null = r.random(rn) < 0.1
    bk[~b_live] = bk[b_live][: (~b_live).sum()] if b_live.any() else 0
    build = from_numpy(
        {"bk": bk, "bv": r.integers(0, 1 << 40, rn),
         "bs": np.array(["x", "y", "z"])[r.integers(0, 3, rn)]},
        valids={"bk": ~b_null, "bv": r.random(rn) < 0.9})
    build = Relation(build.columns, jnp.asarray(b_live))
    pk = r.choice(np.concatenate([bk, r.integers(-4 * rn, 4 * rn, rn)]), ln)
    probe = from_numpy(
        {"pk": pk.astype(np.int64), "pv": np.arange(ln)},
        valids={"pk": r.random(ln) < 0.9})
    probe = Relation(probe.columns, jnp.asarray(r.random(ln) < 0.85))
    return probe, build


def _live_rows(rel):
    """The live rows, NULLs as None (what lies under one is anything),
    sorted."""
    cols = to_numpy(rel)
    names = sorted(n for n in cols if not n.startswith("__valid__"))
    rows = zip(*(
        [x if ok else None for x, ok in zip(
            cols[n].tolist(),
            cols.get("__valid__" + n, np.ones(len(cols[n]), bool)))]
        for n in names))
    return sorted(rows, key=lambda row: tuple((v is None, v) for v in row))


@pytest.mark.parametrize("rank", ["merge", "search"])
@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("ln,rn", [(64, 256), (1000, 333), (4096, 4096),
                                   (5, 1)])
def test_probe_lanes_join_gives_the_expanding_joins_rows(ln, rn, how, rank,
                                                         monkeypatch):
    probe, build = _sides(ln, rn, seed=ln + rn)
    keys = ([ir.col("pk")], [ir.col("bk")])
    want = ops.join(probe, build, *keys, how=how, out_capacity=2 * ln)
    # the shape rule picks the merge above its constant: move the constant
    monkeypatch.setattr(ops, "_MERGE_PROBE_MIN_GATHERS",
                        0 if rank == "merge" else 1 << 40)
    with diag.collect() as lanes, diag.note_collect() as notes:
        got = ops.join(probe, build, *keys, how=how, out_capacity=2 * ln,
                       build_unique=True)
    assert got.capacity == ln          # on the probe's lanes, not 2 x ln
    assert [(n, int(v)) for n, v, _cap in lanes] == [("join_build_dup", 0)]
    assert ("join_emit", "probe_lanes", 1) in notes \
        and ("probe", rank, 1) in notes
    # the probe's columns are the arrays that came in: no gather
    assert got.columns["pv"].data is probe.columns["pv"].data
    assert _live_rows(got) == _live_rows(want)
    if how == "left":
        assert int(got.count()) == int(probe.count())


@pytest.mark.parametrize("rank", ["merge", "search"])
def test_a_repeated_build_key_is_counted(rank, monkeypatch):
    probe, build = _sides(200, 100, seed=7)
    bk = np.array(to_numpy(Relation(build.columns, None))["bk"],
                  dtype=object)
    live = np.asarray(build.mask) & np.array([v is not None for v in bk])
    first, second, third = np.flatnonzero(live)[:3]
    forged = np.where(live, bk, 0).astype(np.int64)
    forged[second] = forged[third] = forged[first]      # one key, 3 rows
    cols = dict(build.columns)
    cols["bk"] = cols["bk"].with_data(jnp.asarray(forged),
                                      cols["bk"].valid)
    monkeypatch.setattr(ops, "_MERGE_PROBE_MIN_GATHERS",
                        0 if rank == "merge" else 1 << 40)
    with diag.collect() as lanes:
        ops.join(probe, Relation(cols, build.mask), [ir.col("pk")],
                 [ir.col("bk")], build_unique=True)
    assert [(n, int(v)) for n, v, _cap in lanes] == [("join_build_dup", 2)]


@pytest.mark.parametrize("n", [1, 5, 1024, 1025, 5000, 393_216])
def test_running_max_is_cummax(n):
    """Two levels, one answer: lane for lane ``np.maximum.accumulate``."""
    r = np.random.default_rng(n)
    x = np.where(r.random(n) < 0.3, r.integers(0, 1 << 62, n), 0)
    got = jax.jit(ops._running_max)(jnp.asarray(x))
    assert got.dtype == jnp.int64
    np.testing.assert_array_equal(np.asarray(got),
                                  np.maximum.accumulate(x))


def test_the_unique_path_merges_from_half_the_gathers():
    """Its merge is one sort more to compile than its search, where
    ``_probe_ranges``' is two: Q14's shapes at SF1 (131,072 compacted
    lanes into ``part``'s 262,144) merge here and would search there."""
    rn = 262_144
    for ln, want in ((131_072, "merge"), (65_536, "search")):
        assert not ops._ranks_by_merge(rn, ln)
        probe = Relation(from_numpy(
            {"pk": np.zeros(8, np.int64)}).columns, None)
        build = Relation(from_numpy(
            {"bk": np.zeros(8, np.int64)}).columns, None)
        shapes = [jax.tree.map(
            lambda x, n=n: jax.ShapeDtypeStruct((n,), x.dtype), rel)
            for rel, n in ((probe, ln), (build, rn))]
        with diag.note_collect() as notes:
            jax.eval_shape(
                lambda p, b: ops.join(p, b, [ir.col("pk")], [ir.col("bk")],
                                      build_unique=True), *shapes)
        assert notes == [("join_kind", "inner", 1), ("probe", want, 1),
                         ("join_emit", "probe_lanes", 1)]


def test_a_join_the_path_cannot_serve_expands(monkeypatch):
    """A composite key is hash-mixed (candidates need verifying), a semi
    join only masks: the mark is not honoured, the answer is the same."""
    probe, build = _sides(64, 64, seed=3)
    two = ([ir.col("pk"), ir.col("pv")], [ir.col("bk"), ir.col("bv")])
    with diag.note_collect() as notes:
        got = ops.join(probe, build, *two, out_capacity=128,
                       build_unique=True)
    assert ("join_emit", "expanded", 1) in notes and got.capacity == 128
    with diag.note_collect() as notes:
        ops.join(probe, build, [ir.col("pk")], [ir.col("bk")], how="semi",
                 build_unique=True)
    assert not [n for n in notes if n[0] == "join_emit"]


# -- (2) the planner ---------------------------------------------------------

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


def _plan(s, sql):
    return s._plan_select(parse_sql(sql), None)[0]


def test_q14_probes_with_the_compact_and_builds_on_part(serial):
    (join,) = _joins(_plan(serial, QUERIES[14]))
    assert join.build_unique and join.how == "inner"
    assert isinstance(join.left, pp.Compact) and join.left.strict
    assert isinstance(join.right, pp.TableScan) \
        and join.right.table == "part"
    assert [k.name.split("_", 1)[1].rsplit("_", 1)[0]
            for k in join.left_keys + join.right_keys] \
        == ["l_partkey", "p_partkey"]
    # the comparison the rule makes: the probe's lanes fit the budget
    assert join.left.capacity <= join.out_capacity
    text = "\n".join(r[0] for r in serial.execute(
        "explain " + QUERIES[14]).rows())
    assert "HashJoin(unique build, on probe lanes, " in text


def test_q3_keeps_its_two_expanding_joins(serial):
    """``customer`` is unique on ``c_custkey``, but ``orders`` probes with
    more lanes than the join's budget; the second join builds on a join's
    output.  Neither is marked, and the plan is the parent's."""
    plan = _plan(serial, QUERIES[3])
    assert [j.build_unique for j in _joins(plan)] == [False, False]
    assert pp.logical_hash(plan) == PARENT_LOGICAL_HASH[3]
    assert "unique build" not in "\n".join(
        r[0] for r in serial.execute("explain " + QUERIES[3]).rows())
    assert "build_unique" not in plan.fingerprint()


@pytest.mark.parametrize("case,sql,marked", [
    ("primary_key",
     "select count(*), sum(bv) from ca, cb where aj = bk", [True]),
    ("left_join_on_the_key",
     "select count(*), sum(bv) from ca left join cb on aj = bk", [True]),
    ("non_key_column",
     "select count(*), sum(bv) from ca, cb where aj = bv", [False]),
    ("composite_key",
     "select count(*), sum(dv) from ca, cd where aj = d1 and av = d2",
     [False]),
    ("probe_wider_than_the_budget",
     "select count(*), sum(bv) from ca, cb where aj = bk and av < 1200",
     [False]),
])
def test_what_the_planner_marks(case, sql, marked, new_session):
    s = new_session()
    rng = np.random.default_rng(39)
    n = 8000
    s.catalog.load_numpy(
        "ca", {"ak": np.arange(n), "aj": rng.integers(0, 500, n),
               "av": rng.integers(0, 4000, n)}, primary_key=["ak"])
    s.catalog.load_numpy(
        "cb", {"bk": np.arange(500), "bv": rng.integers(0, 100, 500)},
        primary_key=["bk"])
    s.catalog.load_numpy(
        "cd", {"d1": np.repeat(np.arange(250), 2),
               "d2": np.tile(np.arange(2), 250),
               "dv": rng.integers(0, 9, 500)}, primary_key=["d1", "d2"])
    for t in ("ca", "cb", "cd"):
        s.execute(f"analyze table {t}")
    joins = _joins(_plan(s, sql))
    assert [j.build_unique for j in joins] == marked, case
    for j in joins:
        if j.build_unique:      # cb builds, whichever way the SQL reads
            assert "bk" in j.right_keys[0].name
    db = sqlite3.connect(":memory:")
    for t in ("ca", "cb", "cd"):
        cols = to_numpy(s.catalog.table_data(t))
        db.execute(f"create table {t} ({', '.join(cols)})")
        db.executemany(
            f"insert into {t} values ({', '.join('?' * len(cols))})",
            list(zip(*(c.tolist() for c in cols.values()))))
    want = [tuple(r) for r in db.execute(sql).fetchall()]
    ok, why = rows_match(s.execute(sql).rows(), want, ordered=True)
    assert ok, why


def test_a_key_under_another_join_is_no_guarantee(serial):
    """``part``'s key survives an N:1 join above it, and the estimates'
    ``unique_cols`` says so; the rule asks for a filter chain over a scan."""
    from oceanbase_tpu.sql.optimizer import unique_build

    (q14,) = _joins(_plan(serial, QUERIES[14]))
    probe, part, key = q14.left, q14.right, q14.right_keys
    cap, cat = q14.out_capacity, serial.catalog
    assert unique_build(probe, part, key, cap, cat)
    assert unique_build(probe, pp.Filter(part, ir.Literal(True)), key, cap,
                        cat)
    assert not unique_build(probe, q14, key, cap, cat)
    assert not unique_build(probe, pp.Project(part, {}), key, cap, cat)
    # the other half of the rule: the probe's static lanes
    assert not unique_build(probe, part, key, probe.capacity // 2, cat)
    # (a projection keeps its child's lanes, PR 44; what no rule of
    # ``_static_lanes`` knows stays "no")
    assert unique_build(pp.Project(probe, {}), part, key, cap, cat)
    assert not unique_build(pp.Union([probe]), part, key, cap, cat)
    assert unique_build(q14, part, key, cap, cat)   # a marked join's lanes


# -- (3) a broken key costs time, never an answer ----------------------------

DUP_SQL = "select count(*), sum(bv), min(av) from fa, fb where aj = bk"


def _forged(s):
    """``fb`` declares ``bk`` its primary key and holds key 7 three times
    (loaded through the direct path, which trusts the caller's keys)."""
    rng = np.random.default_rng(5)
    n = 3000
    bk = np.arange(300)
    bk[[8, 9]] = 7
    fa = {"ak": np.arange(n), "aj": rng.integers(0, 300, n),
          "av": rng.integers(0, 4000, n)}
    fb = {"bk": bk, "bv": rng.integers(0, 100, 300)}
    s.catalog.load_numpy("fa", fa, primary_key=["ak"])
    s.catalog.load_numpy("fb", fb, primary_key=["bk"])
    db = sqlite3.connect(":memory:")
    for t, arrays in (("fa", fa), ("fb", fb)):
        db.execute(f"create table {t} ({', '.join(arrays)})")
        db.executemany(
            f"insert into {t} values ({', '.join('?' * len(arrays))})",
            list(zip(*(a.tolist() for a in arrays.values()))))
    return [tuple(r) for r in db.execute(DUP_SQL).fetchall()]


def test_a_forged_key_is_counted_replanned_and_answered_as_sqlite(tmp_path):
    from oceanbase_tpu.exec.plan import execute_plan

    db = Database(str(tmp_path / "db"))
    s = db.session()
    want = _forged(s)
    plan = _plan(s, DUP_SQL)
    (join,) = _joins(plan)
    assert join.build_unique
    tables = {t: s.catalog.table_data(t) for t in ("fa", "fb")}
    with pytest.raises(CapacityOverflow) as err:
        execute_plan(plan, tables)
    assert err.value.drops == [("join_build_dup", None, 2)]
    # the re-plan: marks off, budgets as they were
    again, step = after_overflow(plan, err.value.drops)
    assert step == 1 and not _joins(again)[0].build_unique
    assert _joins(again)[0].left == join.left
    assert without_unique_builds(again) == again

    retries = qmetrics.counter_value("plan.capacity_retries")
    emits = _emits()
    ok, why = rows_match(s.execute(DUP_SQL).rows(), want, ordered=True)
    assert ok, why
    assert qmetrics.counter_value("plan.capacity_retries") == retries + 1
    # the unmarked plan took the cached one's place: no second re-plan
    ok, why = rows_match(s.execute(DUP_SQL).rows(), want, ordered=True)
    assert ok, why
    assert qmetrics.counter_value("plan.capacity_retries") == retries + 1
    now = _emits()
    assert now["expanded"] - emits["expanded"] == 2
    # EXPLAIN ANALYZE rides the same re-plan and shows the plan that ran
    text = "\n".join(r[0] for r in s.execute(
        "explain analyze " + DUP_SQL.replace("min(av)", "max(av)")).rows())
    assert "HashJoin(" in text and "unique build" not in text
    db.close()


def test_an_overflow_beside_a_repeat_scales_and_unmarks():
    plan = pp.HashJoin(pp.TableScan("a"), pp.TableScan("b"), [], [],
                       out_capacity=64, build_unique=True)
    both = [("join_build_dup", None, 1), ("compact_overflow", 64, 1000)]
    again, step = after_overflow(plan, both)
    assert step >= 4 and not again.build_unique
    # a PX program's total names no lane: any overflow takes the marks off
    again, step = after_overflow(plan, [], jump=False)
    assert step == 4 and not again.build_unique
    kept, step = after_overflow(plan, [("join_overflow", 64, 10)])
    assert step >= 4 and kept.build_unique


# -- (4) the mark travels ------------------------------------------------------

def test_the_mark_survives_the_plan_codec_and_the_hashes(serial):
    plan = _plan(serial, QUERIES[14])
    (join,) = _joins(plan)
    back = dtl.decode_plan(dtl.encode_plan(join))
    assert isinstance(back, pp.HashJoin) and back.build_unique
    assert back.fingerprint() == join.fingerprint()
    plain = without_unique_builds(join)
    assert not dtl.decode_plan(dtl.encode_plan(plain)).build_unique
    assert "build_unique" not in repr(plain) \
        and repr(join).endswith(", build_unique=True)")
    assert pp.logical_hash(plain) != pp.logical_hash(join)


@needs_four
@pytest.mark.parametrize("qnum", [14, 3])
def test_px4_matches_the_oracle_and_books_its_joins(qnum, px4, sqlite):
    """Q14's marked join and Q3's two expanding ones inside shard programs,
    over tables partitioned by DDL: each execution books its joins."""
    want = run_oracle(sqlite, QUERIES[qnum])
    plan = _plan(px4, QUERIES[qnum])
    assert [j.build_unique for j in _joins(plan)] == \
        ([True] if qnum == 14 else [False, False])
    for _ in range(2):
        before = _emits()
        rows = px4.execute(QUERIES[qnum]).rows()
        after = _emits()
        assert px4._last_px
        ok, why = rows_match(rows, want, ordered=True)
        assert ok, why
        assert after["probe_lanes"] - before["probe_lanes"] == \
            (1 if qnum == 14 else 0)
        assert after["expanded"] - before["expanded"] == \
            (0 if qnum == 14 else 2)


# -- (5) the counter, serial ---------------------------------------------------

@pytest.mark.parametrize("qnum,on_probe,expanded", [(14, 1, 0), (3, 0, 2),
                                                    (6, 0, 0)])
def test_join_emits_are_booked_per_execution(qnum, on_probe, expanded,
                                             serial, sqlite):
    want = run_oracle(sqlite, QUERIES[qnum])
    for _ in range(3):
        before = _emits()
        rows = serial.execute(QUERIES[qnum]).rows()
        after = _emits()
        assert not serial._last_px
        assert after["probe_lanes"] - before["probe_lanes"] == on_probe
        assert after["expanded"] - before["expanded"] == expanded
        ok, why = rows_match(rows, want, ordered=True)
        assert ok, why
