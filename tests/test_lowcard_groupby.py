"""Low-cardinality (direct dictionary code) GROUP BY fast path."""

import numpy as np
import pytest

from oceanbase_tpu.exec import ops
from oceanbase_tpu.exec.ops import AggSpec, hash_groupby
from oceanbase_tpu.expr import ir
from oceanbase_tpu.vector import from_numpy, to_numpy


def _run(rel, keys, aggs, force_sort=False, cap=None):
    if force_sort:
        old = ops.LOWCARD_GROUP_LIMIT
        ops.LOWCARD_GROUP_LIMIT = 0
        try:
            return to_numpy(hash_groupby(rel, keys, aggs, out_capacity=cap))
        finally:
            ops.LOWCARD_GROUP_LIMIT = old
    return to_numpy(hash_groupby(rel, keys, aggs, out_capacity=cap))


def _norm(res, cols):
    rows = sorted(zip(*[list(res[c]) for c in cols]))
    return rows


def test_lowcard_matches_sort_path(rng):
    n = 5000
    flag = rng.choice(np.array(["A", "N", "R"]), n)
    status = rng.choice(np.array(["F", "O"]), n)
    nulls = rng.random(n) < 0.1
    v = rng.integers(-100, 100, n)
    rel = from_numpy({"f": flag, "s": status, "v": v},
                     valids={"s": ~nulls})
    keys = {"f": ir.col("f"), "s": ir.col("s")}
    aggs = [AggSpec("sum", "sum", ir.col("v")),
            AggSpec("cnt", "count_star"),
            AggSpec("mn", "min", ir.col("v")),
            AggSpec("av", "avg", ir.col("v"))]
    fast = _run(rel, keys, aggs)
    slow = _run(rel, keys, aggs, force_sort=True)
    cols = ["f", "s", "sum", "cnt", "mn"]
    assert _norm(fast, cols) == _norm(slow, cols)
    np.testing.assert_allclose(sorted(fast["av"]), sorted(slow["av"]))


def test_lowcard_bool_keys(rng):
    n = 1000
    b = rng.integers(0, 2, n).astype(bool)
    v = rng.integers(0, 10, n)
    rel = from_numpy({"b": b, "v": v})
    out = to_numpy(hash_groupby(rel, {"b": ir.col("b")},
                                [AggSpec("s", "sum", ir.col("v"))]))
    got = dict(zip(out["b"], out["s"]))
    assert got[False] == v[~b].sum() and got[True] == v[b].sum()


def test_lowcard_respects_capacity_fallback(rng):
    # out_capacity below the code space must fall back (still correct)
    n = 500
    s = rng.choice(np.array([f"k{i}" for i in range(50)]), n)
    rel = from_numpy({"s": s})
    out = to_numpy(hash_groupby(rel, {"s": ir.col("s")},
                                [AggSpec("c", "count_star")],
                                out_capacity=8))
    # truncated sort-path output of 8 groups (overflow handled upstream)
    assert len(out["s"]) <= 8


# ---------------------------------------------------------------------------
# the masked streaming reductions against the sort path and against the
# scatters of jax.ops.segment_*: one answer three ways
# ---------------------------------------------------------------------------

_ALL_AGGS = [AggSpec("sm", "sum", ir.col("v")),
             AggSpec("ct", "count", ir.col("v")),
             AggSpec("n", "count_star"),
             AggSpec("av", "avg", ir.col("v")),
             AggSpec("lo", "min", ir.col("v")),
             AggSpec("hi", "max", ir.col("v"))]


def _groups(res, keys):
    """{key tuple (None for NULL): {aggregate: value or None}} of a
    ``to_numpy`` result."""
    def cell(name, i):
        valid = res.get("__valid__" + name)
        return None if valid is not None and not valid[i] else res[name][i]

    names = [n for n in res if not n.startswith("__valid__")]
    n = len(res[names[0]]) if names else 0
    return {tuple(cell(k, i) for k in keys):
            {a: cell(a, i) for a in names if a not in keys}
            for i in range(n)}


def _case(name):
    """-> (relation, group keys) of one named input."""
    import jax.numpy as jnp

    r = np.random.default_rng(11)
    # (most inputs are one block of the masked reduce)
    n = {"two_blocks": 2 * ops._MASKED_REDUCE_BLOCK,
         # two rows of a two-level scan and 77 lanes of a third
         "odd_lanes": 2 * 1024 + 77}.get(name, 3000)
    flag = r.choice(np.array(["A", "N", "R"]), n)
    status = r.choice(np.array(["F", "O"]), n)
    v = r.integers(-1000, 1000, n)
    cols, valids, mask = {"f": flag, "s": status, "v": v}, {}, None
    keys = ("f", "s")
    if name == "nullable_keys":
        valids = {"f": r.random(n) > 0.1, "s": r.random(n) > 0.2}
    elif name == "nullable_arguments":
        valids = {"v": r.random(n) > 0.3}
        # one whole group's argument is NULL: its SUM/MIN/MAX/AVG are NULL
        valids["v"] &= ~((flag == "N") & (status == "O"))
    elif name in ("dead_lanes", "two_blocks"):
        valids = {"v": r.random(n) > 0.1, "s": r.random(n) > 0.1}
        mask = r.random(n) > 0.4
    elif name == "all_dead":
        mask = np.zeros(n, bool)
    elif name == "zero_lanes":
        # an empty table that arrives unpadded, with its dictionaries
        rel = from_numpy(cols, valids={"v": r.random(n) > 0.1})
        return rel.gather(jnp.zeros(0, jnp.int32)), keys
    elif name == "bool_keys":
        cols = {"f": r.integers(0, 2, n).astype(bool),
                "s": r.integers(0, 2, n).astype(bool), "v": v}
        valids = {"s": r.random(n) > 0.1}
    elif name == "int64_near_2_62":
        # every group's sum wraps several times; the masked sums and the
        # sort path's scatter must wrap alike
        cols["v"] = r.integers(2 ** 62 - 10 ** 6, 2 ** 62, n) \
            * r.choice(np.array([-1, 1]), n)
    elif name == "float_values":
        cols["v"] = r.normal(0.0, 1e3, n)
        valids = {"v": r.random(n) > 0.1}
    elif name == "forty_codes":
        cols["f"] = r.choice(np.array([f"k{i:02d}" for i in range(40)]), n)
        keys = ("f",)
        del cols["s"]
        valids = {"f": r.random(n) > 0.05}
    elif name == "one_group":
        cols["f"], cols["s"] = np.full(n, "A"), np.full(n, "F")
        valids = {"v": r.random(n) > 0.1}
    elif name == "every_lane_a_group":
        cols["f"] = np.array([f"k{i:04d}" for i in r.permutation(n)])
        keys = ("f",)
        del cols["s"]
        valids = {"v": r.random(n) > 0.1}
    elif name == "odd_lanes":
        valids = {"v": r.random(n) > 0.1, "f": r.random(n) > 0.1}
        mask = r.random(n) > 0.2
    else:
        raise ValueError(name)
    rel = from_numpy(cols, valids=valids)
    if mask is not None:
        rel = rel.with_mask(jnp.asarray(mask))
    return rel, keys


@pytest.mark.parametrize("case", [
    "nullable_keys", "nullable_arguments", "dead_lanes", "all_dead",
    "bool_keys", "int64_near_2_62", "float_values", "forty_codes",
    "two_blocks", "zero_lanes", "one_group", "every_lane_a_group",
    "odd_lanes"])
def test_masked_reduce_matches_sort_path(case):
    from oceanbase_tpu.exec import diag

    rel, keys = _case(case)
    group_by = {k: ir.col(k) for k in keys}
    with diag.note_collect() as notes:
        fast = _groups(_run(rel, group_by, _ALL_AGGS), keys)
    assert notes == [("groupby", "masked", 1)]
    with diag.note_collect() as notes:
        slow = _groups(_run(rel, group_by, _ALL_AGGS, force_sort=True), keys)
    # (and every reduction over the sorted lanes a scan)
    assert notes[0] == ("groupby", "sort", 1)
    assert {n for n in notes if n[0] == "groupby_reduce"} == {
        ("groupby_reduce", "scan", 1)}
    # (and, since PR 44, the lanes it sorts and emits on; since PR 45
    # where its sorted lanes come from: the keys' out of the sort, and
    # ``v``'s, once, gathered: the shape rule at so few lanes)
    assert {n[0] for n in notes[1:]} == {
        "groupby_reduce", "groupby_sort_lanes", "groupby_out_lanes",
        "groupby_sorted_read"}
    assert [n[1] for n in notes if n[0] == "groupby_sorted_read"] == [
        "sort", "gather"]
    assert fast.keys() == slow.keys()
    assert (len(fast) == 0) == (case in ("all_dead", "zero_lanes"))
    for key, want in slow.items():
        for agg, w in want.items():
            g = fast[key][agg]
            if w is None or g is None:
                assert g is None and w is None, (key, agg, g, w)
            elif agg == "av" or case == "float_values":
                assert g == pytest.approx(w, rel=1e-9, abs=1e-9), (key, agg)
            else:
                assert g == w and type(g) is type(w), (key, agg, g, w)


def _by_scatter(rel, keys):
    """``_groups``' answer for ``_ALL_AGGS`` by ``jax.ops.segment_*`` over
    group numbers that NumPy assigns: what the sort path's reductions
    were before they became scans."""
    import jax
    import jax.numpy as jnp

    host = to_numpy(rel)
    lanes = len(host["v"])
    where = {k: host.get("__valid__" + k, np.ones(lanes, bool))
             for k in (*keys, "v")}
    tuples = [tuple(host[k][i] if where[k][i] else None for k in keys)
              for i in range(lanes)]
    numbers = {t: g for g, t in enumerate(dict.fromkeys(tuples))}
    nseg = len(numbers)
    if nseg == 0:
        return {}
    gid = jnp.asarray(np.array([numbers[t] for t in tuples], np.int32))
    v, w = jnp.asarray(host["v"]), jnp.asarray(where["v"])
    zero = jnp.zeros((), v.dtype)

    def seg(fn, d):
        return np.asarray(getattr(jax.ops, "segment_" + fn)(
            d, gid, num_segments=nseg))

    sm = seg("sum", jnp.where(w, v, zero))
    ct = seg("sum", w.astype(jnp.int64))
    n = seg("sum", jnp.ones(lanes, jnp.int64))
    lo = seg("min", jnp.where(w, v, ops._agg_identity("min", v.dtype)))
    hi = seg("max", jnp.where(w, v, ops._agg_identity("max", v.dtype)))
    return {t: {"sm": sm[g] if ct[g] else None, "ct": ct[g], "n": n[g],
                "av": float(sm[g]) / ct[g] if ct[g] else None,
                "lo": lo[g] if ct[g] else None,
                "hi": hi[g] if ct[g] else None}
            for t, g in numbers.items()}


@pytest.mark.parametrize("case", [
    "nullable_keys", "nullable_arguments", "dead_lanes", "all_dead",
    "zero_lanes", "int64_near_2_62", "float_values", "one_group",
    "every_lane_a_group", "odd_lanes", "bool_keys", "forty_codes"])
def test_sort_path_reduces_as_the_scatters_did(case, monkeypatch):
    """The sort path's scans and boundary compaction against
    ``jax.ops.segment_*``: integer sums and counts bit-equal (every
    group's sum of ``int64_near_2_62`` wraps several times), float sums to
    rounding, NULL where a group has no value, the groups dense at the
    front and in key order."""
    monkeypatch.setattr(ops, "LOWCARD_GROUP_LIMIT", 0)
    rel, keys = _case(case)
    out = hash_groupby(rel, {k: ir.col(k) for k in keys}, _ALL_AGGS)
    got, want = _groups(to_numpy(out), keys), _by_scatter(rel, keys)
    assert got.keys() == want.keys()
    live = np.asarray(out.mask)
    assert live[:len(want)].all() and not live[len(want):].any()
    for key, w in want.items():
        for agg, x in w.items():
            g = got[key][agg]
            if x is None:
                assert g is None, (key, agg, g)
            elif agg == "av" or case == "float_values":
                assert g == pytest.approx(x, rel=1e-12, abs=1e-9), (key, agg)
            else:
                assert g == x and g.dtype == x.dtype, (key, agg, g, x)
    # key order: codes ascending, a NULL key behind every value
    codes = [tuple((np.inf if c is None else c) for c in
                   (_code(out, k, i) for k in keys))
             for i in range(len(want))]
    assert codes == sorted(codes)
    assert out.columns["sm"].data.dtype == rel.columns["v"].data.dtype
    # a key leaves the sort as it went in: type, width and dictionary
    for k in keys:
        assert out.columns[k].dtype == rel.columns[k].dtype
        assert out.columns[k].data.dtype == rel.columns[k].data.dtype
        assert out.columns[k].sdict is rel.columns[k].sdict


def _sort_then_gather(keys, payloads=()):
    """``_sort_with_rows`` as the group-by used it before PR 45: a sort of
    the keys and the row numbers alone, and every other lane (the sorted
    keys too) read through the row numbers."""
    import jax.numpy as jnp

    rows = _SORT_WITH_ROWS(keys)[-1]
    return (*(jnp.take(k, rows) for k in reversed(tuple(keys))), rows,
            *(jnp.take(p, rows) for p in payloads))


_SORT_WITH_ROWS = ops._sort_with_rows

#: name -> (aggregates, the ``groupby_sorted_read`` notes besides the
#: keys' own, the columns that ride the sort as payloads)
_READS = {
    "all_aggs": (_ALL_AGGS, ["sort"], ["v"]),
    # aggregates over one argument share one payload, spelled twice or not
    "shared_argument": ([AggSpec("sm", "sum", ir.col("v") * 2),
                         AggSpec("av", "avg", ir.col("v") * 2),
                         AggSpec("lo", "min", ir.col("v"))],
                        ["sort", "sort"], ["v", "v"]),
    # an argument that is a group key is read from the sorted key
    "argument_is_a_key": ([AggSpec("sm", "sum", ir.col("f")),
                           AggSpec("hi", "max", ir.col("f")),
                           AggSpec("ct", "count", ir.col("f")),
                           AggSpec("n", "count_star")], [], []),
    "count_star_alone": ([AggSpec("n", "count_star")], [], []),
    # count(distinct) sorts again and gathers, as before
    "count_distinct": ([AggSpec("d", "count_distinct", ir.col("v")),
                        AggSpec("sm", "sum", ir.col("v"))],
                       ["sort", "gather"], ["v"]),
}


@pytest.mark.parametrize("reads", list(_READS))
@pytest.mark.parametrize("case", [
    "nullable_keys", "nullable_arguments", "dead_lanes", "all_dead",
    "zero_lanes", "int64_near_2_62", "float_values", "bool_keys",
    "forty_codes"])
def test_sort_path_reads_its_sorted_lanes_from_its_sort(case, reads,
                                                        monkeypatch):
    """The keys, the live flag and the arguments as the group-by's own
    sort returns them (the shape rule lifted: at these few lanes an
    argument would be gathered) against the same lanes gathered through
    the sort's row numbers (the parent's reads): every output bit-equal,
    DOUBLE sums (``float_values``) included, since ties still break by row
    number and a group's values are added in one order; the dictionary of
    a string key survives; one payload a distinct argument, none for a
    key."""
    import jax
    from oceanbase_tpu.exec import diag

    monkeypatch.setattr(ops, "LOWCARD_GROUP_LIMIT", 0)
    monkeypatch.setattr(ops, "_RIDE_MIN_READS", 0)
    monkeypatch.setattr(ops, "_RIDE_MAX_SORT_OPERANDS", 64)
    rel, keys = _case(case)
    aggs, noted, ridden = _READS[reads]
    group_by = {k: ir.col(k) for k in keys}

    def run(r):
        return hash_groupby(r, group_by, aggs)

    with diag.note_collect() as notes:
        jaxpr = jax.make_jaxpr(run)(rel)
    assert [n[1] for n in notes if n[0] == "groupby_sorted_read"] == [
        "sort", *noted]
    # the group-by's own sort: dead flag, keys with their validity, row
    # number, and behind them one payload a distinct argument (with its
    # validity); count(distinct)'s re-sort takes its argument as a key
    nullable = {c: rel.columns[c].valid is not None for c in ("v", *keys)}
    width = 2 + sum(1 + nullable[k] for k in keys)
    sorts = sorted(len(e.invars) for e in jaxpr.eqns
                   if e.primitive.name == "sort")
    assert width + sum(1 + nullable[c] for c in ridden) in sorts
    assert sorts[-1] == max(
        width + sum(1 + nullable[c] for c in ridden),
        (width + 1) * (reads == "count_distinct"))

    new = run(rel)
    monkeypatch.setattr(ops, "_sort_with_rows", _sort_then_gather)
    old = run(rel)
    assert new.columns.keys() == old.columns.keys()
    np.testing.assert_array_equal(np.asarray(new.mask), np.asarray(old.mask))
    for name, a in new.columns.items():
        b = old.columns[name]
        assert a.dtype == b.dtype and a.data.dtype == b.data.dtype, name
        assert a.sdict is b.sdict, name
        assert (a.valid is None) == (b.valid is None), name
        # bit for bit: a float sum's every bit too
        assert np.asarray(a.data).tobytes() == np.asarray(b.data).tobytes(), \
            name
        if a.valid is not None:
            np.testing.assert_array_equal(np.asarray(a.valid),
                                          np.asarray(b.valid), err_msg=name)


@pytest.mark.parametrize("lanes, keys, arg, nullable, rides", [
    # Q18's subquery: l_orderkey, sum(l_quantity) over lineitem's lanes
    (67_108_864, ["int64"], "int64", False, True),
    # Q13's first: c_custkey, count(o_orderkey) behind the outer join
    (33_554_432, ["int64"], "int64", True, True),
    # a 16.8M-lane group-by of an int32 argument: at the constant
    (16_777_216, ["int64"], "int32", False, True),
    (16_777_216 - 1, ["int64"], "int32", False, False),
    # Q9's local group-by a shard, Q3's: too few lanes for the compiler's
    # seconds
    (1_966_080, ["int32", "int32"], "int64", False, False),
    (524_288, ["int64", "int32", "int32"], "int64", False, False),
    # Q18's last: five keys are ten operands before the argument's two
    (8_388_608, ["int32", "int64", "int64", "int32", "int64"], "int64",
     False, False),
    (67_108_864, ["int32", "int64", "int64", "int32", "int64"], "int64",
     False, False),
    # a bool key is sorted as an int64: six operands, and eight or nine
    # with the argument
    (67_108_864, ["bool", "int64"], "int64", False, True),
    (67_108_864, ["bool", "int64"], "int64", True, False),
])
def test_the_shape_rule_of_a_riding_argument(lanes, keys, arg, nullable,
                                             rides, monkeypatch):
    """``_rides_sort`` at the cells' shapes (no array is made: the notes
    of an abstract trace say which way each argument went)."""
    import jax
    import jax.numpy as jnp
    from oceanbase_tpu.datatypes import SqlType
    from oceanbase_tpu.exec import diag
    from oceanbase_tpu.vector import Column, Relation

    monkeypatch.setattr(ops, "LOWCARD_GROUP_LIMIT", 0)

    def lane(dtype):
        return jax.ShapeDtypeStruct((lanes,), jnp.dtype(dtype))

    types = {"bool": SqlType.bool_(), "int32": SqlType.int_(),
             "int64": SqlType.int_()}
    cols = {f"k{i}": Column(lane(t), None, types[t])
            for i, t in enumerate(keys)}
    cols["v"] = Column(lane(arg), lane("bool") if nullable else None,
                       types[arg])
    rel = Relation(cols, lane("bool"))
    with diag.note_collect() as notes:
        jax.eval_shape(lambda r: hash_groupby(
            r, {k: ir.col(k) for k in cols if k != "v"},
            [AggSpec("s", "sum", ir.col("v")),
             AggSpec("a", "avg", ir.col("v"))], out_capacity=1024), rel)
    assert [n[1] for n in notes if n[0] == "groupby_sorted_read"] == [
        "sort", "sort" if rides else "gather"]


def _code(out, name, i):
    c = out.columns[name]
    if c.valid is not None and not bool(c.valid[i]):
        return None
    return int(c.data[i])


@pytest.mark.parametrize("cap", [1, 4, 5, 6, 64])
def test_sort_path_reports_groups_over_its_capacity(cap, monkeypatch):
    """``out_capacity`` below the group count (``nullable_keys`` makes 12
    groups: 3 x 2 codes and their NULLs): the overflow lane counts the
    groups that did not fit, and the first ``cap`` groups are right."""
    monkeypatch.setattr(ops, "LOWCARD_GROUP_LIMIT", 0)
    rel, keys = _case("nullable_keys")
    group_by = {k: ir.col(k) for k in keys}
    whole = hash_groupby(rel, group_by, _ALL_AGGS)
    cut, over = hash_groupby(rel, group_by, _ALL_AGGS, out_capacity=cap,
                             return_overflow=True)
    groups = int(np.asarray(whole.mask).sum())
    assert groups == 12
    assert int(over) == max(groups - cap, 0)
    assert cut.capacity == cap
    kept = min(cap, groups)
    assert int(np.asarray(cut.mask).sum()) == kept
    a, b = to_numpy(whole), to_numpy(cut)
    for name in a:
        np.testing.assert_array_equal(a[name][:kept], b[name], err_msg=name)


@pytest.mark.parametrize("lanes", [0, 3000, 2 * ops._MASKED_REDUCE_BLOCK])
@pytest.mark.parametrize("fn", ["sum", "min", "max"])
def test_lowcard_reduce_is_bit_equal_to_the_scatter(fn, lanes):
    """``_lowcard_reduce`` against ``jax.ops.segment_<fn>``, which it
    replaced: int64 values that wrap in a sum, 7 and 4,097 segments (the
    most ``LOWCARD_GROUP_LIMIT`` admits), no lanes at all."""
    import jax
    import jax.numpy as jnp

    r = np.random.default_rng(14)
    d = jnp.asarray(r.integers(-2 ** 62, 2 ** 62, lanes))
    scatter = getattr(jax.ops, "segment_" + fn)
    for nseg in (7, ops.LOWCARD_GROUP_LIMIT + 1):
        gid = jnp.asarray(r.integers(0, nseg, lanes).astype(np.int32))
        got = ops._lowcard_reduce(fn, d, gid, nseg)
        want = scatter(d, gid, num_segments=nseg)[:nseg - 1]
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_groupby_kind_follows_the_code_space(monkeypatch):
    """``hash_groupby`` reads the keys' static code space alone: a
    dictionary on each side of ``LOWCARD_GROUP_LIMIT`` (brought down for
    this), the same answers either way."""
    from oceanbase_tpu.exec import diag

    monkeypatch.setattr(ops, "LOWCARD_GROUP_LIMIT", 32)
    r = np.random.default_rng(12)
    n = 2000
    v = r.integers(-50, 50, n)
    for codes, want in ((32, "masked"), (33, "sort")):
        names = np.array([f"k{i:02d}" for i in range(codes)])
        k = names[r.integers(0, codes, n)]
        k[:codes] = names
        rel = from_numpy({"k": k, "v": v})
        with diag.note_collect() as notes:
            got = _groups(_run(rel, {"k": ir.col("k")}, _ALL_AGGS), ("k",))
        assert [x for x in notes if x[0] == "groupby"] \
            == [("groupby", want, 1)], (codes, notes)
        slow = _groups(_run(rel, {"k": ir.col("k")}, _ALL_AGGS,
                            force_sort=True), ("k",))
        assert got.keys() == slow.keys() and len(got) == codes
        for key, w in slow.items():
            assert got[key] == pytest.approx(w), key


def test_q1_shaped_groupby_lowers_without_a_scatter():
    """Two dictionary keys (3 x 2 codes), sums, averages and a count over
    the lanes: no scatter in the lowered program (what the TPU's compiler
    keeps in memory for it: tests/test_tpu_compile.py)."""
    import jax

    r = np.random.default_rng(13)
    n = 4096
    rel = from_numpy({"f": r.choice(np.array(["A", "N", "R"]), n),
                      "s": r.choice(np.array(["F", "O"]), n),
                      "q": r.integers(0, 50, n),
                      "p": r.integers(0, 10 ** 6, n)})
    aggs = [AggSpec("sq", "sum", ir.col("q")),
            AggSpec("sp", "sum", ir.col("p")),
            AggSpec("sd", "sum", ir.col("p") * ir.col("q")),
            AggSpec("aq", "avg", ir.col("q")),
            AggSpec("ap", "avg", ir.col("p")),
            AggSpec("n", "count_star")]
    keys = {"f": ir.col("f"), "s": ir.col("s")}
    lowered = jax.jit(lambda rel: hash_groupby(rel, keys, aggs)).lower(rel)
    assert "stablehlo.scatter" not in lowered.as_text()
    assert " scatter(" not in lowered.compile().as_text()
