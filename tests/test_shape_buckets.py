"""Shape-bucketed relation capacities (the static-shape policy).

Covers the three contracts the bucket ladder rests on:

1. padded-lane semantics — every operator treats pad lanes as dead, so a
   relation at exact vs bucket-padded capacity yields identical results
   (aggregates, group-by, joins, sorts, top-N, NULL lanes, empty tables);
2. compile amortization — a table grown through several increments
   inside one bucket compiles its plan exactly once, and exactly twice
   across a bucket boundary (exec.plan trace counters / gv$plan_cache);
3. the session plan cache evicts LRU (move-to-front on hit, oldest out)
   honoring plan_cache_mem_limit.
"""

import numpy as np
import pytest

from oceanbase_tpu.exec import ops
from oceanbase_tpu.exec.ops import AggSpec
from oceanbase_tpu.expr import ir
from oceanbase_tpu.vector import Relation, bucket_capacity, from_numpy, to_numpy


# ---------------------------------------------------------------------------
# the ladder
# ---------------------------------------------------------------------------


def test_bucket_ladder():
    assert bucket_capacity(0) == 64
    assert bucket_capacity(1) == 64
    assert bucket_capacity(64) == 64
    assert bucket_capacity(65) == 128
    assert bucket_capacity(1000) == 1024
    assert bucket_capacity(1 << 20) == 1 << 20
    # custom floor/growth
    assert bucket_capacity(5, floor=8, growth=2.0) == 8
    assert bucket_capacity(100, floor=10, growth=3.0) == 270
    # a degenerate growth factor still terminates and rounds up
    assert bucket_capacity(100, floor=4, growth=1.0) >= 100


def test_pad_to_always_materializes_mask():
    rel = from_numpy({"a": np.arange(8)})
    assert rel.mask is None
    same = rel.pad_to(8)
    assert same.mask is not None and bool(np.asarray(same.mask).all())
    padded = rel.pad_to(16)
    assert padded.capacity == 16
    assert int(np.asarray(padded.mask).sum()) == 8
    with pytest.raises(ValueError):
        rel.pad_to(4)


def test_string_dict_content_equality():
    from oceanbase_tpu.vector.column import StringDict

    a = StringDict(np.array(["a", "b", "c"], dtype=object))
    b = StringDict(np.array(["a", "b", "c"], dtype=object))
    c = StringDict(np.array(["a", "b", "d"], dtype=object))
    assert a == b and hash(a) == hash(b)
    assert a != c


# ---------------------------------------------------------------------------
# padded-lane semantics: exact vs bucket-padded capacity
# ---------------------------------------------------------------------------


def _sample_rel():
    return from_numpy(
        {
            "k": np.array([1, 2, 1, 3, 2, 1, 4], dtype=np.int64),
            "v": np.array([10, 20, 30, 40, 50, 60, 70], dtype=np.int64),
            "f": np.array([1.5, 2.5, 3.5, 4.5, 5.5, 6.5, 7.5]),
            "s": np.array(["x", "y", "x", "z", "y", "x", "w"],
                          dtype=object),
        },
        valids={"v": np.array([1, 1, 0, 1, 1, 1, 0], dtype=bool)},
    )


def _rows(rel, names=None):
    out = to_numpy(rel)
    names = names or sorted(k for k in out if not k.startswith("__valid__"))
    rows = []
    n = len(out[names[0]]) if names else 0
    for i in range(n):
        row = []
        for nm in names:
            v = out.get("__valid__" + nm)
            row.append(None if v is not None and not v[i]
                       else out[nm][i])
        rows.append(tuple(row))
    return rows


CASES = [
    ("count_star", lambda r: ops.scalar_agg(
        r, [AggSpec("c", "count_star", None)])),
    ("sum", lambda r: ops.scalar_agg(r, [AggSpec("s", "sum", ir.col("v"))])),
    ("avg", lambda r: ops.scalar_agg(r, [AggSpec("a", "avg", ir.col("f"))])),
    ("count_col", lambda r: ops.scalar_agg(
        r, [AggSpec("c", "count", ir.col("v"))])),
    ("min_max", lambda r: ops.scalar_agg(
        r, [AggSpec("lo", "min", ir.col("v")),
            AggSpec("hi", "max", ir.col("v"))])),
    ("group_by", lambda r: ops.hash_groupby(
        r, {"k": ir.col("k")},
        [AggSpec("s", "sum", ir.col("v")),
         AggSpec("c", "count_star", None)], out_capacity=16)),
    ("group_by_str", lambda r: ops.hash_groupby(
        r, {"s": ir.col("s")},
        [AggSpec("c", "count_star", None)], out_capacity=16)),
    ("order_by", lambda r: ops.sort_rows(
        r, [ir.col("k"), ir.col("v")], [True, False])),
    ("top_n", lambda r: ops.top_n(r, ir.col("f"), False, 3)),
    ("filter", lambda r: ops.filter_rows(
        r, ir.Cmp(">", ir.col("k"), ir.Literal(1)))),
]


@pytest.mark.parametrize("name,fn", CASES, ids=[c[0] for c in CASES])
def test_padded_lanes_invisible(name, fn):
    rel = _sample_rel()
    padded = rel.pad_to(bucket_capacity(rel.capacity))
    assert padded.capacity == 64
    a = _rows(fn(rel))
    b = _rows(fn(padded))
    if name in ("group_by", "group_by_str"):
        a, b = sorted(a), sorted(b)
    assert a == b


@pytest.mark.parametrize("name,fn", CASES, ids=[c[0] for c in CASES])
def test_poisoned_padded_lanes_invisible(name, fn, poison):
    """The poison-lane verifier over the parity suite: adversarial
    garbage in pad lanes (NaN payloads, sentinel ints, validity flipped
    true) must leave every operator's result bit-identical."""
    from oceanbase_tpu.vector import to_numpy as _to_np

    padded = _sample_rel().pad_to(64)
    clean = _to_np(fn(padded))
    poisoned = _to_np(fn(poison.poison_pad_lanes(padded)))
    ok, why = poison.results_identical(clean, poisoned)
    assert ok, f"{name}: {why}"


def test_poisoned_join_matches_clean(poison):
    left = _sample_rel().pad_to(64)
    right = from_numpy({
        "k2": np.array([1, 2, 5], dtype=np.int64),
        "w": np.array([100, 200, 500], dtype=np.int64),
    }).pad_to(64)
    for how in ("inner", "left", "semi", "anti"):
        clean = ops.join(left, right, [ir.col("k")], [ir.col("k2")],
                         how=how, out_capacity=64)
        pois = ops.join(poison.poison_pad_lanes(left),
                        poison.poison_pad_lanes(right),
                        [ir.col("k")], [ir.col("k2")],
                        how=how, out_capacity=64)
        assert sorted(_rows(clean), key=repr) == \
            sorted(_rows(pois), key=repr), how


def test_padded_join_matches_exact():
    left = _sample_rel()
    right = from_numpy({
        "k2": np.array([1, 2, 5], dtype=np.int64),
        "w": np.array([100, 200, 500], dtype=np.int64),
    })
    exact = ops.join(left, right, [ir.col("k")], [ir.col("k2")],
                     how="inner", out_capacity=64)
    padded = ops.join(left.pad_to(64), right.pad_to(64),
                      [ir.col("k")], [ir.col("k2")],
                      how="inner", out_capacity=64)
    assert sorted(_rows(exact)) == sorted(_rows(padded))
    # outer join: pad lanes must not emit NULL-extended ghost rows
    exact_l = ops.join(left, right, [ir.col("k")], [ir.col("k2")],
                       how="left", out_capacity=64)
    padded_l = ops.join(left.pad_to(64), right.pad_to(64),
                        [ir.col("k")], [ir.col("k2")],
                        how="left", out_capacity=64)
    assert sorted(_rows(exact_l), key=repr) == \
        sorted(_rows(padded_l), key=repr)


def test_empty_table_bucketed(tmp_path):
    """_empty_rel pads to the floor bucket, all lanes dead, and queries
    over it behave as over an empty table."""
    from oceanbase_tpu.server import Database

    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table e (k int primary key, v int)")
    rel = db.tenant("sys").catalog.table_data("e")
    assert rel.capacity == 64  # floor bucket
    assert int(np.asarray(rel.mask).sum()) == 0
    r = s.execute("select count(*), sum(v) from e")
    assert r.rows() == [(0, None)]
    db.close()


# ---------------------------------------------------------------------------
# compile amortization: trace counters
# ---------------------------------------------------------------------------


def test_trace_count_within_and_across_buckets(tmp_path):
    """10 growth increments inside one bucket -> exactly one XLA trace;
    crossing the bucket boundary -> exactly one more."""
    from oceanbase_tpu.exec import plan as ep
    from oceanbase_tpu.server import Database

    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table t (id int primary key, v int)")
    q = "select sum(v), count(*) from t"
    nid = 0

    def grow(n):
        nonlocal nid
        vals = ", ".join(f"({nid + i}, {(nid + i) % 7})" for i in range(n))
        nid += n
        s.execute(f"insert into t values {vals}")

    ep.reset_plan_cache_stats()
    expect = []
    for _ in range(10):
        grow(5)  # 10 increments, 50 rows total: all inside bucket 64
        expect.append(s.execute(q).rows())
    stats = ep.plan_cache_stats()
    assert sum(e.xla_traces for e in stats) == 1
    assert sum(e.executions for e in stats) == 10
    assert max(e.last_compile_s for e in stats) > 0

    grow(30)  # 80 rows: bucket 64 -> 128
    r = s.execute(q)
    stats = ep.plan_cache_stats()
    assert sum(e.xla_traces for e in stats) == 2
    assert r.rows()[0][1] == 80

    # gv$plan_cache serves the same counters (snapshot taken before the
    # gv$ query itself executes)
    before = sum(e.xla_traces for e in ep.plan_cache_stats())
    r = s.execute("select xla_trace_count, executions, hit_count "
                  "from gv$plan_cache")
    assert sum(int(x[0]) for x in r.rows()) == before
    db.close()


def test_disable_shape_buckets_retraces(tmp_path):
    """With the knob off, every cardinality change retraces (the old
    behavior stays reachable)."""
    from oceanbase_tpu.exec import plan as ep
    from oceanbase_tpu.server import Database

    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("alter system set enable_shape_buckets = false")
    s.execute("create table t (id int primary key, v int)")
    q = "select sum(v) from t"
    nid = 0
    ep.reset_plan_cache_stats()
    for _ in range(3):
        vals = ", ".join(f"({nid + i}, 1)" for i in range(5))
        nid += 5
        s.execute(f"insert into t values {vals}")
        s.execute(q)
    stats = ep.plan_cache_stats()
    assert sum(e.xla_traces for e in stats) == 3
    rel = db.tenant("sys").catalog.table_data("t")
    assert rel.capacity == 15  # exact, no padding
    db.close()


def test_row_count_is_live_not_padded(tmp_path):
    from oceanbase_tpu.server import Database

    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table t (id int primary key, v int)")
    s.execute("insert into t values " +
              ", ".join(f"({i}, {i})" for i in range(10)))
    s.execute("select * from t")  # materializes (padded to 64)
    td = db.tenant("sys").catalog.table_def("t")
    assert td.row_count == 10  # live rows, not the bucket capacity
    db.close()


def test_ann_runtime_handles_bucket_padded_suffix(new_session):
    """Bucket padding adds a dead SUFFIX; the ANN runtime slices it off
    instead of disabling the index access path."""
    rng = np.random.default_rng(3)
    vecs = rng.normal(size=(100, 8)).astype(np.float32)
    s = new_session()
    s.catalog.load_numpy("emb", {"id": np.arange(100), "v": vecs},
                         primary_key=["id"])
    rel = s.catalog.table_data("emb").pad_to(bucket_capacity(100))
    idx = s._ann_runtime("emb", "v", "l2", rel)
    assert idx is not None and np.asarray(idx).shape == (100, 8)
    # interior dead rows still bail (would need an id remap)
    holed = rel.with_mask(rel.mask_or_true().at[3].set(False))
    s.catalog._ann_cache.clear()
    assert s._ann_runtime("emb", "v", "l2", holed) is None


# ---------------------------------------------------------------------------
# session plan cache: real LRU honoring plan_cache_mem_limit
# ---------------------------------------------------------------------------


def test_plan_cache_lru_eviction(tmp_path):
    from oceanbase_tpu.server import Database

    db = Database(str(tmp_path / "db"))
    s = db.session()
    s.execute("create table t (id int primary key, v int)")
    s.execute("insert into t values (1, 1), (2, 2)")
    # measure one entry, then budget for two-and-a-half of them
    s.execute("select v from t where id = 0")
    per_entry = s._plan_cache_total
    assert per_entry > 0
    limit = int(2.5 * per_entry)
    s.execute(f"alter system set plan_cache_mem_limit = {limit}")
    s.plan_cache.clear()
    s._plan_cache_bytes.clear()
    s._plan_cache_total = 0
    s.execute("select v from t where id = 1")
    s.execute("select v from t where id = 2")
    assert len(s.plan_cache) == 2
    keys = list(s.plan_cache)
    s.execute("select v from t where id = 1")  # LRU touch: 1 to front
    assert list(s.plan_cache)[-1] == keys[0]
    s.execute("select v from t where id = 3")  # evicts the oldest (id=2)
    assert keys[1] not in s.plan_cache
    assert keys[0] in s.plan_cache
    assert s._plan_cache_total <= limit
    db.close()
