"""The result boundary (``vector/column.py::to_numpy``): a relation leaves
the device through one cached pack program, sized by its live rows, in a
number of transfers that follows from neither its columns nor its
capacity.  Held here to the per-column fetch it replaced, array for array;
to the regime its shape rules pick; to the transfer count; and to
compiling nothing at a signature it has seen.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from oceanbase_tpu.datatypes import SqlType, TypeKind
from oceanbase_tpu.server import Database
from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.vector import Column, Relation, from_numpy, to_numpy
from oceanbase_tpu.vector import column as vcol

SPARSE_LANES = 524288


def per_column(rel, limit=None):
    """The fetch ``to_numpy`` made before PR 34, kept as the reference:
    the mask and every column whole, one transfer each, the live rows
    picked on the host."""
    mask = np.asarray(rel.mask_or_true())
    out = {}
    idx = np.nonzero(mask)[0]
    if limit is not None:
        idx = idx[:limit]
    for name, col in rel.columns.items():
        data = np.asarray(col.data)[idx]
        if col.dtype.kind == TypeKind.VECTOR:
            out[name] = np.array([data[i] for i in range(len(data))],
                                 dtype=object)
            if col.valid is not None:
                out.setdefault("__valid__" + name,
                               np.asarray(col.valid)[idx])
            continue
        if col.sdict is not None:
            data = col.sdict.values[np.clip(data, 0, col.sdict.size - 1)]
        if col.valid is not None:
            v = np.asarray(col.valid)[idx]
            data = np.where(v, data, None) if data.dtype == object else data
            out[name] = data
            out.setdefault("__valid__" + name, v)
        else:
            out[name] = data
    return out


def assert_same(got, want):
    assert list(got) == list(want)
    for name, w in want.items():
        g = got[name]
        assert g.dtype == w.dtype and g.shape == w.shape, name
        if w.dtype == object:
            for a, b in zip(g, w):
                if isinstance(b, np.ndarray):
                    assert a.dtype == b.dtype and np.array_equal(a, b), name
                else:
                    assert a is b or a == b, name
        elif w.dtype.kind == "f":
            # bit for bit, NaN payloads and signed zeros included
            assert g.view(np.int64).tolist() == w.view(np.int64).tolist()
        else:
            assert np.array_equal(g, w), name


def _every_kind(n, rng):
    """One column of each kind the engine holds, ``n`` rows."""
    floats = rng.normal(size=n)
    floats[::7] = np.nan
    floats[1::11] = -0.0
    arrays = {
        "i": rng.integers(-2**62, 2**62, n),
        "dec": rng.integers(-10**14, 10**14, n),
        "d": rng.integers(8000, 12000, n).astype(np.int32),
        "f": floats,
        "s": np.array([f"s{v % 13}" for v in range(n)], dtype=object),
        "ni": rng.integers(-5, 5, n),
        "ns": np.array([f"t{v % 5}" for v in range(n)], dtype=object),
        "b": rng.integers(0, 2, n).astype(np.bool_),
        "v": rng.normal(size=(n, 4)).astype(np.float32),
    }
    types = {"dec": SqlType.decimal(15, 2), "d": SqlType.date(),
             "v": SqlType.vector(4)}
    valids = {"ni": rng.integers(0, 3, n) > 0,
              "ns": rng.integers(0, 4, n) > 0,
              "v": rng.integers(0, 5, n) > 0}
    return from_numpy(arrays, types=types, valids=valids)


def _masked(rel, mask):
    return rel.with_mask(None if mask is None else jnp.asarray(mask))


def _cases():
    rng = np.random.default_rng(34)
    few = _every_kind(40, rng)
    yield "every_kind_masked", _masked(few, rng.integers(0, 2, 40) > 0), None
    yield "every_kind_no_mask", few, None
    yield "empty_result", _masked(few, np.zeros(40, bool)), None
    yield "all_live", _masked(few, np.ones(40, bool)), None
    yield "limit", _masked(few, np.arange(40) % 3 > 0), 5
    yield "limit_zero", _masked(few, np.arange(40) % 3 > 0), 0
    yield "limit_over_rows", _masked(few, np.arange(40) % 3 > 0), 1000
    yield "one_lane", _every_kind(1, rng), None
    mid = _every_kind(6000, rng)       # over the whole-pack rule
    sparse = np.zeros(6000, bool)
    sparse[rng.choice(6000, 37, replace=False)] = True
    yield "mid_sparse", _masked(mid, sparse), None
    yield "mid_sparse_limit", _masked(mid, sparse), 3
    yield "mid_dense", _masked(mid, rng.integers(0, 8, 6000) > 0), None
    yield "mid_dense_limit", _masked(mid, rng.integers(0, 8, 6000) > 0), 9
    yield "mid_no_mask", mid, None
    yield "mid_empty", _masked(mid, np.zeros(6000, bool)), None
    # Q3's shape: ten rows alive on 524,288 lanes, four 64-bit columns
    big = from_numpy({
        "k": np.arange(SPARSE_LANES), "rev": np.arange(SPARSE_LANES) * 7,
        "day": np.arange(SPARSE_LANES) % 2000, "pri": np.zeros(
            SPARSE_LANES, np.int64)}, types={"rev": SqlType.decimal(15, 4)})
    ten = np.zeros(SPARSE_LANES, bool)
    ten[rng.choice(SPARSE_LANES, 10, replace=False)] = True
    yield "ten_of_524288", _masked(big, ten), None
    yield "dense_524288", _masked(big, np.arange(SPARSE_LANES) % 5 > 0), None


CASES = {name: (rel, limit) for name, rel, limit in _cases()}


@pytest.mark.parametrize("name", list(CASES))
def test_equal_to_the_per_column_fetch(name):
    rel, limit = CASES[name]
    assert_same(to_numpy(rel, limit=limit), per_column(rel, limit=limit))


def _tags(rel, limit=None):
    tags = {}
    to_numpy(rel, limit=limit, tags=tags)
    return tags


@pytest.mark.parametrize("name, kind, transfers", [
    # at most _AS_IT_LIES_MAX_LANES lanes: as it lies, no count round
    # trip: the mask, nine columns, three validities
    ("every_kind_masked", "dense", 13),
    ("one_lane", "dense", 12),
    # more lanes, few alive: the count, then the planes of its bucket,
    # one an element type (int64, float64) and the VECTOR's own
    ("mid_sparse", "packed", 4),
    ("ten_of_524288", "packed", 2),
    # more lanes, most alive: the count, then mask and arrays as they lie
    ("dense_524288", "dense", 6),
    ("mid_no_mask", "dense", 12),
])
def test_the_regime_follows_from_the_relation(name, kind, transfers):
    rel, limit = CASES[name]
    tags = _tags(rel, limit)
    assert (tags["kind"], tags["transfers"]) == (kind, transfers)
    assert tags["capacity"] == rel.capacity
    assert tags["rows"] == len(next(iter(per_column(rel, limit).values())))


def test_the_shape_rules_at_their_edges():
    lanes = vcol._AS_IT_LIES_MAX_LANES
    share = vcol._DENSIFY_MAX_SHARE

    def rel(capacity, live):
        return _masked(from_numpy({"a": np.arange(capacity)}),
                       np.arange(capacity) < live)

    assert _tags(rel(lanes, 1)) == {"kind": "dense", "rows": 1,
                                    "capacity": lanes, "bytes": lanes * 9,
                                    "transfers": 2}
    over = rel(lanes * 2, 1)
    assert _tags(over) == {"kind": "packed", "rows": 1,
                           "capacity": lanes * 2, "bytes": 4 + 2 * 64 * 8,
                           "transfers": 2}
    bucket = lanes * 2 // share                  # the largest that packs
    assert _tags(rel(lanes * 2, bucket))["kind"] == "packed"
    assert _tags(rel(lanes * 2, bucket + 1))["kind"] == "dense"
    # a LIMIT shrinks the bucket that crosses
    assert _tags(rel(lanes * 2, lanes), limit=3)["kind"] == "packed"


def _nullable(ncols, capacity, live=6):
    return _masked(from_numpy(
        {f"c{i}": np.arange(capacity) + i for i in range(ncols)},
        valids={f"c{i}": np.arange(capacity) % 2 > 0
                for i in range(ncols)}), np.arange(capacity) < live)


def test_a_packed_relations_transfers_do_not_grow_with_its_columns():
    one = _tags(_nullable(1, SPARSE_LANES))
    ten = _tags(_nullable(10, SPARSE_LANES))
    # the count and the one int64 plane
    assert one["transfers"] == ten["transfers"] == 2
    assert one["bytes"] == 4 + 3 * 64 * 8 and ten["bytes"] == 4 + 21 * 64 * 8


@pytest.mark.parametrize("ncols", [1, 10])
def test_a_small_relations_copies_are_requested_together(ncols,
                                                         monkeypatch):
    """A transfer waited for alone is a round trip; one of many requested
    together a sixth of one (PERF.md section 6, PR 34): a relation that
    crosses as it lies asks for all its arrays in ONE ``device_get``."""
    asked = []
    device_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda tree: asked.append(
        len(jax.tree_util.tree_leaves(tree))) or device_get(tree))
    tags = _tags(_nullable(ncols, 8))
    assert asked == [1 + 2 * ncols] and tags["transfers"] == asked[0]
    assert tags["kind"] == "dense"


@pytest.mark.parametrize("capacity, started", [
    (8, 5), (vcol._AS_IT_LIES_MAX_LANES, 5),
    # over the rule nothing is asked for before its count is known
    (vcol._AS_IT_LIES_MAX_LANES * 2, 0)])
def test_prefetch_starts_a_small_relations_copies(capacity, started,
                                                  monkeypatch):
    from jax._src.array import ArrayImpl

    rel = _nullable(2, capacity)
    calls = []
    real = ArrayImpl.copy_to_host_async
    monkeypatch.setattr(ArrayImpl, "copy_to_host_async",
                        lambda self: calls.append(self) or real(self))
    vcol.prefetch(rel)
    assert len(calls) == started
    monkeypatch.undo()
    assert_same(to_numpy(rel), per_column(rel))


def test_a_second_fetch_at_a_signature_compiles_nothing():
    rng = np.random.default_rng(5)

    def ten_alive():
        mask = np.zeros(SPARSE_LANES, bool)
        mask[rng.choice(SPARSE_LANES, 10, replace=False)] = True
        return _masked(from_numpy({"a": rng.integers(0, 9, SPARSE_LANES),
                                   "x": rng.normal(size=SPARSE_LANES)}),
                       mask)

    to_numpy(ten_alive())
    to_numpy(_every_kind(3, rng))
    compiles = qmetrics.counter_value("plan.compiles")
    # other values, other column names' worth of dictionaries, the same
    # shapes and count bucket
    assert len(to_numpy(ten_alive())["a"]) == 10
    to_numpy(_every_kind(3, rng))
    assert qmetrics.counter_value("plan.compiles") == compiles
    # another count bucket is another program
    mask = np.arange(SPARSE_LANES) < 100
    to_numpy(_masked(ten_alive(), mask))
    assert qmetrics.counter_value("plan.compiles") == compiles + 1


def test_the_counters_say_which_regime_and_how_many_bytes():
    def fetched():
        return ({k: qmetrics.counter_value("sql.result_fetches", kind=k)
                 for k in ("packed", "dense", "columns")},
                qmetrics.counter_value("sql.result_fetch_bytes"))

    kinds, nbytes = fetched()
    tags = [_tags(CASES[name][0]) for name in
            ("every_kind_masked", "ten_of_524288", "dense_524288")]
    host = Relation({"a": Column(np.arange(4), None, SqlType.int_())},
                    np.arange(4) > 1)
    assert to_numpy(host)["a"].tolist() == [2, 3]
    tags.append(_tags(host))
    assert tags[-1]["kind"] == "columns" and tags[-1]["transfers"] == 0
    after, after_bytes = fetched()
    assert {k: after[k] - kinds[k] for k in kinds} == {
        "packed": 1, "dense": 2, "columns": 2}
    assert after_bytes - nbytes == sum(t["bytes"] for t in tags) \
        + tags[-1]["bytes"]


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    db = Database(str(tmp_path_factory.mktemp("fetch") / "db"))
    s = db.session()
    s.execute("create table t (k int primary key, g varchar(8), "
              "v decimal(12,2), d date, n int)")
    s.execute("insert into t values " + ", ".join(
        f"({i}, 'g{i % 3}', {i}.25, date '1995-01-{1 + i % 28:02d}', "
        f"{'null' if i % 4 == 0 else i})" for i in range(50)))
    yield s
    db.close()


def _materialize_tags(s):
    import json

    for row in s.execute("show trace").rows():
        if row[0].strip() == "materialize":
            return json.loads(row[4])
    raise AssertionError("no materialize span")


def test_a_statement_tags_its_materialize_span(session):
    res = session.execute("select k, g, v, d, n from t where k < 7 "
                          "order by k")
    assert res.rowcount == 7
    assert res.rows()[4] == (4, "g1", 4.25, "1995-01-05", None)
    tags = _materialize_tags(session)
    assert tags["kind"] == "dense" and tags["rows"] == 7
    # the mask, and data and validity of each of five columns
    assert tags["transfers"] == 11 and tags["bytes"] > 0
    assert tags["capacity"] >= 7


def test_duplicate_output_names_and_sysstat(session):
    res = session.execute("select k, k, n as k from t where k = 4")
    assert res.names == ["k", "k_2", "k_3"]
    assert res.rows() == [(4, 4, None)]
    stats = dict(session.execute(
        "select stat_name, value from gv$sysstat "
        "where stat_name like 'sql.result_fetch%'").rows())
    assert stats["sql.result_fetches{kind=dense}"] >= 1
    assert stats["sql.result_fetch_bytes"] > 0


def test_an_overflow_still_raises_before_a_result_exists(session):
    """The fetch follows ``execute_plan``'s overflow check: nothing of a
    truncated relation reaches the host."""
    from oceanbase_tpu.exec import diag, plan as pp
    from oceanbase_tpu.sql.binder import Binder
    from oceanbase_tpu.sql.parser import parse_sql

    plan, _outs, _est = Binder(session.catalog).bind_select(parse_sql(
        "select a.k, b.k from t a join t b on a.g = b.g"))
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, pp.HashJoin):
            node.out_capacity = 64          # 834 pairs do not fit
        stack.extend(node.children())
    tables = {"t": session.catalog.table_data("t")}
    fetches = qmetrics.counter_value("sql.result_fetches", kind="dense")
    with pytest.raises(diag.CapacityOverflow):
        pp.execute_plan(plan, tables)
    assert qmetrics.counter_value("sql.result_fetches",
                                  kind="dense") == fetches


needs_four = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 devices")


@needs_four
@pytest.mark.parametrize("live", [10, 3000])
def test_a_relation_spread_over_a_mesh_packs_where_it_lies(live):
    """The PX coordinator's relation: its arrays lie over four devices
    (some sharded, the mask replicated), and the same programs run over
    them, compiled for that placement."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:4]), ("px",))
    lanes = 4 * 4096
    rng = np.random.default_rng(live)
    rel = from_numpy({"a": rng.integers(0, 99, lanes),
                      "x": rng.normal(size=lanes)},
                     valids={"a": rng.integers(0, 2, lanes) > 0})
    mask = np.zeros(lanes, bool)
    mask[rng.choice(lanes, live, replace=False)] = True
    spread = jax.device_put(rel, NamedSharding(mesh, P("px")))
    spread = spread.with_mask(jax.device_put(
        jnp.asarray(mask), NamedSharding(mesh, P())))
    tags = {}
    assert_same(to_numpy(spread, tags=tags),
                per_column(_masked(rel, mask)))
    assert tags["kind"] == ("packed" if live == 10 else "dense")
    # the same shapes on one device are another placement, not a clash
    assert_same(to_numpy(_masked(rel, mask)), per_column(_masked(rel, mask)))
