"""Operator unit tests vs numpy/python oracles.

≙ unittest/sql/engine operator tests with the fake table scan feeding
synthetic vectors (unittest/sql/engine/ob_fake_table_scan_vec_op.h)."""

import numpy as np
import pytest

from oceanbase_tpu.exec import (
    AggSpec,
    compact,
    filter_rows,
    hash_groupby,
    join,
    limit,
    scalar_agg,
    sort_rows,
)
from oceanbase_tpu.expr import ir
from oceanbase_tpu.vector import from_numpy, to_numpy


def test_filter_and_compact(rng):
    n = 5000
    rel = from_numpy({"a": rng.integers(0, 100, n), "b": rng.integers(0, 5, n)})
    a = np.asarray(rel.columns["a"].data)
    out = filter_rows(rel, ir.col("a") < 30)
    assert int(out.count()) == int((a < 30).sum())
    c = compact(out)
    got = to_numpy(c)["a"]
    np.testing.assert_array_equal(np.sort(got), np.sort(a[a < 30]))


def test_groupby_sums(rng):
    n = 10000
    a = rng.integers(0, 7, n)
    v = rng.integers(-50, 50, n)
    rel = from_numpy({"g": a, "v": v})
    out = hash_groupby(
        rel,
        {"g": ir.col("g")},
        [
            AggSpec("s", "sum", ir.col("v")),
            AggSpec("c", "count_star"),
            AggSpec("mn", "min", ir.col("v")),
            AggSpec("mx", "max", ir.col("v")),
            AggSpec("av", "avg", ir.col("v")),
        ],
        out_capacity=64,
    )
    res = to_numpy(out)
    order = np.argsort(res["g"])
    for k in res:
        res[k] = res[k][order]
    keys = np.unique(a)
    np.testing.assert_array_equal(res["g"], keys)
    np.testing.assert_array_equal(res["s"], [v[a == k].sum() for k in keys])
    np.testing.assert_array_equal(res["c"], [(a == k).sum() for k in keys])
    np.testing.assert_array_equal(res["mn"], [v[a == k].min() for k in keys])
    np.testing.assert_array_equal(res["mx"], [v[a == k].max() for k in keys])
    np.testing.assert_allclose(res["av"], [v[a == k].mean() for k in keys])


def test_groupby_multi_key_with_nulls(rng):
    n = 2000
    g1 = rng.integers(0, 3, n)
    g2 = rng.integers(0, 4, n)
    nulls = rng.random(n) < 0.1
    v = rng.integers(0, 100, n)
    rel = from_numpy({"g1": g1, "g2": g2, "v": v},
                     valids={"g2": ~nulls})
    out = hash_groupby(rel, {"g1": ir.col("g1"), "g2": ir.col("g2")},
                       [AggSpec("c", "count_star")])
    res = to_numpy(out)
    # oracle: nulls form their own group per g1
    import collections
    oracle = collections.Counter()
    for i in range(n):
        key = (g1[i], None if nulls[i] else g2[i])
        oracle[key] += 1
    assert len(res["g1"]) == len(oracle)
    got_total = res["c"].sum()
    assert got_total == n


def test_count_distinct(rng):
    n = 3000
    g = rng.integers(0, 5, n)
    v = rng.integers(0, 20, n)
    rel = from_numpy({"g": g, "v": v})
    out = hash_groupby(rel, {"g": ir.col("g")},
                       [AggSpec("cd", "count_distinct", ir.col("v"))],
                       out_capacity=16)
    res = to_numpy(out)
    order = np.argsort(res["g"])
    np.testing.assert_array_equal(
        res["cd"][order], [len(np.unique(v[g == k])) for k in np.unique(g)]
    )


def test_scalar_agg_empty_and_nulls():
    rel = from_numpy({"x": np.array([1, 2, 3, 4])},
                     valids={"x": np.array([True, True, False, False])})
    rel = filter_rows(rel, ir.col("x") < 0)  # empty
    out = scalar_agg(rel, [AggSpec("c", "count", ir.col("x")),
                           AggSpec("s", "sum", ir.col("x")),
                           AggSpec("n", "count_star")])
    res = to_numpy(out)
    assert res["c"][0] == 0 and res["n"][0] == 0
    assert not np.asarray(out.columns["s"].valid)[0]  # SUM of empty = NULL


def test_inner_join_pk_fk(rng):
    nl, nr = 5000, 200
    fk = rng.integers(0, nr, nl)
    lval = rng.integers(0, 1000, nl)
    rval = rng.integers(0, 1000, nr)
    left = from_numpy({"fk": fk, "lv": lval})
    right = from_numpy({"pk": np.arange(nr), "rv": rval})
    out = join(left, right, [ir.col("fk")], [ir.col("pk")], how="inner",
               out_capacity=nl)
    res = to_numpy(out)
    assert len(res["fk"]) == nl
    np.testing.assert_array_equal(res["fk"], res["pk"])
    np.testing.assert_array_equal(res["rv"], rval[res["fk"]])


def test_join_duplicates_and_semi_anti(rng):
    left = from_numpy({"k": np.array([1, 2, 3, 4]), "lv": np.array([10, 20, 30, 40])})
    right = from_numpy({"rk": np.array([2, 2, 3, 9]), "rv": np.array([1, 2, 3, 4])})
    out = join(left, right, [ir.col("k")], [ir.col("rk")], how="inner",
               out_capacity=16)
    res = to_numpy(out)
    pairs = sorted(zip(res["k"].tolist(), res["rv"].tolist()))
    assert pairs == [(2, 1), (2, 2), (3, 3)]

    semi = join(left, right, [ir.col("k")], [ir.col("rk")], how="semi")
    np.testing.assert_array_equal(np.sort(to_numpy(semi)["k"]), [2, 3])

    anti = join(left, right, [ir.col("k")], [ir.col("rk")], how="anti")
    np.testing.assert_array_equal(np.sort(to_numpy(anti)["k"]), [1, 4])


def test_left_join(rng):
    left = from_numpy({"k": np.array([1, 2, 3]), "lv": np.array([10, 20, 30])})
    right = from_numpy({"rk": np.array([2, 2]), "rv": np.array([7, 8])})
    out = join(left, right, [ir.col("k")], [ir.col("rk")], how="left",
               out_capacity=8)
    res = to_numpy(out)
    assert sorted(res["k"].tolist()) == [1, 2, 2, 3]
    rv_valid = np.asarray(out.columns["rv"].valid)[
        np.nonzero(np.asarray(out.mask_or_true()))[0]]
    assert rv_valid.sum() == 2  # only the two matched rows have rv


def test_multikey_join(rng):
    n = 1000
    k1 = rng.integers(0, 10, n)
    k2 = rng.integers(0, 10, n)
    left = from_numpy({"a1": k1, "a2": k2, "lv": np.arange(n)})
    rk1 = np.repeat(np.arange(10), 10)
    rk2 = np.tile(np.arange(10), 10)
    right = from_numpy({"b1": rk1, "b2": rk2, "rv": np.arange(100)})
    out = join(left, right, [ir.col("a1"), ir.col("a2")],
               [ir.col("b1"), ir.col("b2")], how="inner", out_capacity=n)
    res = to_numpy(out)
    assert len(res["a1"]) == n  # every (k1,k2) pair exists exactly once
    np.testing.assert_array_equal(res["a1"], res["b1"])
    np.testing.assert_array_equal(res["a2"], res["b2"])
    np.testing.assert_array_equal(res["rv"], res["a1"] * 10 + res["a2"])


def test_sort_and_limit(rng):
    n = 1000
    a = rng.integers(0, 100, n)
    b = rng.integers(0, 100, n)
    rel = from_numpy({"a": a, "b": b})
    out = limit(sort_rows(rel, [ir.col("a"), ir.col("b")], [True, False]), 10)
    res = to_numpy(out)
    oracle = sorted(zip(a.tolist(), (-b).tolist()))[:10]
    got = list(zip(res["a"].tolist(), (-res["b"]).tolist()))
    assert got == oracle


def test_join_string_keys_different_dicts():
    left = from_numpy({"name": np.array(["fr", "de", "us", "cn"]),
                       "lv": np.array([1, 2, 3, 4])})
    right = from_numpy({"rname": np.array(["de", "us", "jp"]),
                        "rv": np.array([10, 20, 30])})
    out = join(left, right, [ir.col("name")], [ir.col("rname")], how="inner",
               out_capacity=8)
    res = to_numpy(out)
    pairs = sorted(zip(res["name"].tolist(), res["rv"].tolist()))
    assert pairs == [("de", 10), ("us", 20)]


# ---------------------------------------------------------------------------
# _probe_ranges: one contract (searchsorted left and right, lane for lane),
# two ways to compute it
# ---------------------------------------------------------------------------

_BIG = np.iinfo(np.int64).max  # ops._INT_MAX: a dead build row
_LOW = np.iinfo(np.int64).min


def _probe_case(name):
    """-> (sorted build keys, probe keys), both int64."""
    r = np.random.default_rng(11)
    if name == "duplicates":
        live = np.sort(r.integers(0, 40, 300))
        probe = r.integers(0, 40, 1000)
    elif name == "absent_keys":
        live = np.sort(r.choice(np.arange(0, 2000, 2), 257, replace=False))
        probe = r.integers(-50, 2100, 777)  # odd keys and both ends miss
    elif name == "empty_live_build":
        live = np.zeros(0, np.int64)
        probe = r.integers(-5, 5, 100)
    elif name == "negative_and_sentinel_neighbours":
        live = np.sort(np.array(
            [_LOW, _LOW, _LOW + 1, -7, -7, -1, 0, 1, _BIG - 3, _BIG - 2,
             _BIG - 2, _BIG - 1], np.int64))
        probe = np.array(
            [_LOW, _LOW + 1, _LOW + 2, -8, -7, -6, -1, 0, 1, 2, _BIG - 4,
             _BIG - 3, _BIG - 2, _BIG - 1, _BIG], np.int64)
    elif name == "odd_sizes":
        live = np.sort(r.integers(-1000, 1000, 37))
        probe = r.integers(-1000, 1000, 11)
    elif name == "one_build_row":
        live = np.array([5], np.int64)
        probe = np.array([4, 5, 6, 5], np.int64)
    else:
        raise ValueError(name)
    live, probe = live.astype(np.int64), probe.astype(np.int64)
    if name != "one_build_row":
        # dead build rows sort last, dead and NULL-key probe lanes just
        # below them (join's and index_probe's sentinels)
        live = np.concatenate([live, np.full(13, _BIG)])
        probe[r.random(len(probe)) < 0.15] = _BIG - 1
    return live, probe


@pytest.mark.parametrize("path", ["merge", "search"])
@pytest.mark.parametrize("case", [
    "duplicates", "absent_keys", "empty_live_build",
    "negative_and_sentinel_neighbours", "odd_sizes", "one_build_row"])
def test_probe_ranges_equal_searchsorted(case, path):
    import jax
    import jax.numpy as jnp

    from oceanbase_tpu.exec import ops

    build, probe = map(jnp.asarray, _probe_case(case))
    got = jax.jit(lambda b, p: ops._probe_ranges(b, p, _path=path))(
        build, probe)
    for side, have in zip(("left", "right"), got):
        want = jnp.searchsorted(build, probe, side=side)
        assert have.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(have), np.asarray(want),
                                      err_msg=side)


def test_probe_path_follows_static_shapes():
    """The helper picks from shapes alone, and notes the kind it picked."""
    import jax
    import jax.numpy as jnp

    from oceanbase_tpu.exec import diag, ops

    rn = 1 << 10
    above = ops._MERGE_PROBE_MIN_GATHERS // 10 + 1
    for ln, want in ((above, "merge"), (above - 2, "search")):
        with diag.note_collect() as notes:
            jax.eval_shape(ops._probe_ranges,
                           jax.ShapeDtypeStruct((rn,), jnp.int64),
                           jax.ShapeDtypeStruct((ln,), jnp.int64))
        assert notes == [("probe", want, 1)], (ln, notes)


def _outer_join_relations(masked):
    """The shapes of tests/test_outer_joins.py (aj in 0..80, bj in
    40..120: both sides have unmatched rows and duplicates), with NULL
    keys and, when ``masked``, dead lanes on both sides."""
    from oceanbase_tpu.vector.column import Relation

    r = np.random.default_rng(3)
    na, nb = 300, 200
    left = from_numpy({"ak": np.arange(na), "aj": r.integers(0, 80, na),
                       "a2": r.integers(0, 3, na),
                       "av": r.integers(0, 1000, na)},
                      valids={"aj": r.random(na) > 0.05})
    right = from_numpy({"bk": np.arange(nb), "bj": r.integers(40, 120, nb),
                        "b2": r.integers(0, 3, nb),
                        "bv": r.integers(0, 1000, nb)},
                       valids={"bj": r.random(nb) > 0.05})
    if masked:
        import jax.numpy as jnp

        left = Relation(left.columns, jnp.asarray(
            np.arange(left.capacity) % 7 != 0) & left.mask_or_true())
        right = Relation(right.columns, jnp.asarray(
            np.arange(right.capacity) % 5 != 0) & right.mask_or_true())
    return left, right


def _relation_arrays(rel):
    out = {"__mask__": np.asarray(rel.mask_or_true())}
    for name, c in rel.columns.items():
        out[name] = np.asarray(c.data)
        out[name + ".valid"] = np.asarray(c.valid_or_true())
    return out


@pytest.mark.parametrize("keys", ["exact", "hash_combined"])
@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti", "full"])
def test_join_same_relation_above_and_below_threshold(how, keys,
                                                      monkeypatch):
    """A join whose probe merges and one whose probe searches give the
    same relation, array for array (row order included)."""
    from oceanbase_tpu.exec import diag, ops

    left, right = _outer_join_relations(masked=True)
    if keys == "exact":
        lk, rk = [ir.col("aj")], [ir.col("bj")]
    else:
        lk, rk = ([ir.col("aj"), ir.col("a2")],
                  [ir.col("bj"), ir.col("b2")])
    got = {}
    for kind, floor in (("search", ops._MERGE_PROBE_MIN_GATHERS),
                        ("merge", 0)):
        monkeypatch.setattr(ops, "_MERGE_PROBE_MIN_GATHERS", floor)
        with diag.note_collect() as notes:
            rel = join(left, right, lk, rk, how=how, out_capacity=4096)
        # (the joins that pair rows also note how they emit: join_emit)
        assert [n for n in notes if n[0] == "probe"] == [("probe", kind, 1)]
        got[kind] = _relation_arrays(rel)
    assert sorted(got["merge"]) == sorted(got["search"])
    for name, want in got["search"].items():
        np.testing.assert_array_equal(got["merge"][name], want,
                                      err_msg=name)
    assert got["merge"]["__mask__"].sum() > 0


@pytest.mark.parametrize("how", ["inner", "left", "full", "semi", "anti"])
def test_merge_probe_poison_parity(how, poison, monkeypatch):
    """The merge path is a data-reading operator's inside: garbage in
    masked-dead lanes of either side must not reach the result."""
    from oceanbase_tpu.exec import ops

    monkeypatch.setattr(ops, "_MERGE_PROBE_MIN_GATHERS", 0)
    left, right = _outer_join_relations(masked=True)

    def live_values(rel):
        # what a client can see: a NULL's payload is not part of the
        # answer (a NULL-extended lane carries some build row's payload,
        # on either probe path)
        res = to_numpy(rel)
        for name in [n for n in res if not n.startswith("__valid__")]:
            v = res.get("__valid__" + name)
            if v is not None:
                res[name] = np.where(v, res[name], 0)
        return res

    poison.assert_poison_invariant(
        lambda t: join(t["a"], t["b"], [ir.col("aj")], [ir.col("bj")],
                       how=how, out_capacity=4096),
        {"a": left, "b": right}, materialize=live_values)
