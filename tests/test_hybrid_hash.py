"""HYBRID_HASH skew handling: hot join keys bypass the hash exchange
(VERDICT r3 missing #6; ≙ ObSliceIdxCalc::HYBRID_HASH_{BROADCAST,RANDOM},
src/sql/engine/px/ob_slice_calc.h:73-88).
"""

import numpy as np
import pytest


@pytest.fixture()
def skewed(new_session):
    rng = np.random.default_rng(5)
    n = 40_000
    # 80% of probe rows carry ONE key — a plain hash exchange funnels
    # them into a single destination shard
    hot = rng.random(n) < 0.8
    j = np.where(hot, 7, rng.integers(100, 5000, n))
    s = new_session()
    s.catalog.load_numpy("probe", {
        "k": np.arange(n), "j": j,
        "v": rng.integers(0, 100, n)}, primary_key=["k"])
    nb = 6000
    s.catalog.load_numpy("build", {
        "bj": np.arange(nb), "w": rng.integers(0, 10, nb)},
        primary_key=["bj"])
    return s, j


def test_skewed_join_distributes_correctly(skewed):
    s, j = skewed
    sql = ("select count(*) as c, sum(v + w) as sv "
           "from probe join build on j = bj")
    serial = s.execute(sql).rows()
    s.variables["px_dop"] = 8
    try:
        dist = s.execute(sql).rows()
        assert s._last_px, "skewed join should still run on PX"
    finally:
        s.variables["px_dop"] = 0
    assert serial == dist


def test_hot_key_detection():
    import jax

    from oceanbase_tpu.expr import ir
    from oceanbase_tpu.px.dist_ops import _HOT_SENTINEL, _global_hot_keys
    from oceanbase_tpu.px.exchange import (
        default_mesh,
        shard_relation,
    )
    from oceanbase_tpu.vector import from_numpy

    rng = np.random.default_rng(0)
    n = 4096
    keys = np.where(rng.random(n) < 0.5, 42,
                    rng.integers(1000, 9000, n))
    keys = np.where(rng.random(n) < 0.2, 77, keys)
    rel = from_numpy({"j": keys})
    mesh = default_mesh(8)
    sharded = shard_relation(rel, mesh)

    def body(r):
        hot, _k, _m = _global_hot_keys(r, [ir.col("j")], 4, "px")
        return hot

    from jax.sharding import PartitionSpec as P

    out = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P("px"),), out_specs=P("px"),
        check_vma=False))(sharded)
    hot = set(np.asarray(out).reshape(8, -1)[0].tolist())
    hot.discard(_HOT_SENTINEL)
    assert 42 in hot and 77 in hot


def test_skewed_semi_and_left_joins(skewed):
    s, j = skewed
    for sql in (
        "select count(*) from probe where j in (select bj from build)",
        "select count(*), sum(w) from probe left join build on j = bj",
    ):
        serial = s.execute(sql).rows()
        s.variables["px_dop"] = 8
        try:
            dist = s.execute(sql).rows()
        finally:
            s.variables["px_dop"] = 0
        assert serial == dist, sql
