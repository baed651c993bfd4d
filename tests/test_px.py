"""Distributed (mesh) execution tests on the virtual 8-device CPU mesh.

≙ mittest tier (SURVEY §4 tier 3): real multi-worker wiring in one process.
"""

import jax
import numpy as np
import pytest

from oceanbase_tpu.exec.ops import AggSpec
from oceanbase_tpu.expr import ir
from oceanbase_tpu.px.dist_ops import dist_groupby, dist_join_shard
from oceanbase_tpu.px.exchange import (
    default_mesh,
    shard_relation,
    unshard_relation,
)
from oceanbase_tpu.vector import from_numpy, to_numpy


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= 8, "conftest must provide 8 CPU devices"
    return default_mesh(8)


def test_dist_groupby_matches_local(rng, mesh):
    n = 4096
    g = rng.integers(0, 37, n)
    v = rng.integers(-100, 100, n)
    rel = from_numpy({"g": g, "v": v})
    out = dist_groupby(
        rel, {"g": ir.col("g")},
        [AggSpec("s", "sum", ir.col("v")),
         AggSpec("c", "count_star"),
         AggSpec("mx", "max", ir.col("v")),
         AggSpec("av", "avg", ir.col("v"))],
        mesh, local_cap=64, out_cap=64,
    )
    res = to_numpy(out)
    order = np.argsort(res["g"])
    keys = np.unique(g)
    np.testing.assert_array_equal(res["g"][order], keys)
    np.testing.assert_array_equal(res["s"][order], [v[g == k].sum() for k in keys])
    np.testing.assert_array_equal(res["c"][order], [(g == k).sum() for k in keys])
    np.testing.assert_array_equal(res["mx"][order], [v[g == k].max() for k in keys])
    np.testing.assert_allclose(res["av"][order], [v[g == k].mean() for k in keys])


def test_dist_join_matches_local(rng, mesh):
    from functools import partial

    from jax.sharding import PartitionSpec as P

    nl, nr = 2048, 256
    fk = rng.integers(0, nr, nl)
    left = from_numpy({"fk": fk, "lv": np.arange(nl)})
    right = from_numpy({"pk": np.arange(nr), "rv": rng.integers(0, 1000, nr)})

    ls = shard_relation(left, mesh)
    rs = shard_relation(right, mesh)

    def fn(l, r):
        out, local_ovf = dist_join_shard(
            l, r, left_keys=[ir.col("fk")], right_keys=[ir.col("pk")],
            ndev=8, cap_per_dest=nl // 4, out_capacity=nl, how="inner")
        return out, jax.lax.psum(local_ovf, "px")

    run = jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P("px"), P("px")), out_specs=(P("px"), P()),
        check_vma=False,
    ))
    shard_out, overflow = run(ls, rs)
    assert int(overflow) == 0
    out = unshard_relation(shard_out)
    res = to_numpy(out)
    assert len(res["fk"]) == nl
    np.testing.assert_array_equal(res["fk"], res["pk"])
    rv = np.asarray(right.columns["rv"].data)
    np.testing.assert_array_equal(res["rv"], rv[res["fk"]])
