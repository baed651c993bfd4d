"""Distributed operators: partial-agg + repartition + final-agg, dist join.

Reference analog: the two-DFO group-by / join shapes the PX planner emits
(partial agg DFO -> HASH exchange -> final agg DFO; ob_dfo_mgr.h:19 splits
at ObLogExchange boundaries).  Here each "DFO pair + exchange" is one
shard_map'd function; the exchange is an all_to_all inside it.

Aggregate split mirrors the reference's partial/final aggregate rewrite
(ObHashGroupByVecOp in a PX plan computes partials; the final DFO merges):
    sum   -> sum of partial sums        count -> sum of partial counts
    min   -> min of partial mins        max   -> max of partial maxs
    avg   -> sum(psum)/sum(pcount) as a post-projection
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from oceanbase_tpu.exec.ops import AggSpec, hash_groupby
from oceanbase_tpu.expr import ir
import numpy as np

from oceanbase_tpu.expr.compile import eval_expr
from oceanbase_tpu.px.exchange import (
    PX_AXIS,
    all_to_all_repartition,
    broadcast_gather,
    exchange_by_dest,
    shard_relation,
    unshard_relation,
)
from oceanbase_tpu.vector.column import Relation


def split_aggs(aggs: Sequence[AggSpec]):
    """-> (partial_specs, final_specs, post_projection exprs)."""
    partial_specs: list[AggSpec] = []
    final_specs: list[AggSpec] = []
    post: dict[str, ir.Expr] = {}
    for a in aggs:
        if a.fn in ("sum", "count", "count_star"):
            pname = f"__p_{a.name}"
            if a.fn == "count_star":
                partial_specs.append(AggSpec(pname, "count_star"))
            else:
                partial_specs.append(AggSpec(pname, a.fn, a.arg))
            final_specs.append(AggSpec(a.name, "sum", ir.col(pname)))
            post[a.name] = ir.col(a.name)
        elif a.fn in ("min", "max"):
            pname = f"__p_{a.name}"
            partial_specs.append(AggSpec(pname, a.fn, a.arg))
            final_specs.append(AggSpec(a.name, a.fn, ir.col(pname)))
            post[a.name] = ir.col(a.name)
        elif a.fn == "avg":
            ps, pc = f"__ps_{a.name}", f"__pc_{a.name}"
            partial_specs.append(AggSpec(ps, "sum", a.arg))
            partial_specs.append(AggSpec(pc, "count", a.arg))
            fs, fc = f"__fs_{a.name}", f"__fc_{a.name}"
            final_specs.append(AggSpec(fs, "sum", ir.col(ps)))
            final_specs.append(AggSpec(fc, "sum", ir.col(pc)))
            post[a.name] = ir.Arith("/", ir.col(fs), ir.col(fc))
        else:
            raise NotImplementedError(f"distributed {a.fn}")
    return partial_specs, final_specs, post


def dist_groupby_shard(
    rel: Relation,
    keys: dict[str, ir.Expr],
    aggs: Sequence[AggSpec],
    ndev: int,
    local_cap: int,
    out_cap: int,
    axis_name: str = PX_AXIS,
):
    """Per-shard body (call inside shard_map): partial agg -> all_to_all by
    group-key hash -> final agg.  Each chip ends up owning a disjoint set of
    groups.  Returns (relation, global overflow count) — overflow > 0 means
    an exchange buffer was too small and rows were dropped; callers must
    fail or re-plan (see exec/diag.py)."""
    partial_specs, final_specs, post = split_aggs(aggs)
    local, l_ovf = hash_groupby(rel, keys, partial_specs,
                                out_capacity=local_cap, return_overflow=True)
    key_cols = [ir.col(k) for k in keys]
    recv, x_ovf = all_to_all_repartition(
        local, key_cols, ndev, cap_per_dest=local_cap, axis_name=axis_name,
        kind="groupby")
    final, f_ovf = hash_groupby(
        recv, {k: ir.col(k) for k in keys}, final_specs,
        out_capacity=out_cap, return_overflow=True,
    )
    # post-projection (avg) keeping group key columns
    from oceanbase_tpu.exec.ops import project  # local import to avoid cycle

    outs = {k: ir.col(k) for k in keys}
    outs.update(post)
    # LOCAL overflow count: callers needing a replicated/global value psum
    # it themselves (avoids double-psum when composed, see px/planner.py)
    return project(final, outs), l_ovf + x_ovf + f_ovf


def dist_groupby(
    rel: Relation,
    keys: dict[str, ir.Expr],
    aggs: Sequence[AggSpec],
    mesh,
    local_cap: int = 4096,
    out_cap: int = 4096,
) -> Relation:
    """Host entry: shard a relation over the mesh, run the distributed
    group-by, return the merged (unsharded) result relation."""
    axis = mesh.axis_names[0]
    ndev = mesh.devices.size
    sharded = shard_relation(rel, mesh, axis)

    def fn(rel):
        out, local_ovf = dist_groupby_shard(
            rel, keys=keys, aggs=aggs, ndev=ndev,
            local_cap=local_cap, out_cap=out_cap, axis_name=axis)
        return out, jax.lax.psum(local_ovf, axis)

    spec = P(axis)
    run = jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=(spec,), out_specs=(spec, P()),
            check_vma=False,
        )
    )
    out, overflow = run(sharded)
    # enqueue the gather before the overflow check: the host sync on the
    # count then overlaps the device-side unshard instead of gating it
    rel = unshard_relation(out)
    n_over = int(overflow)  # obcheck: ok(trace.host-sync)
    if n_over > 0:
        from oceanbase_tpu.exec.diag import CapacityOverflow

        raise CapacityOverflow(
            f"exchange buffer overflow: {n_over} rows dropped; "
            f"increase local_cap"
        )
    return rel


_HOT_SENTINEL = np.iinfo(np.int64).max


def _global_hot_keys(rel: Relation, keys: Sequence[ir.Expr],
                     n_hot: int, axis_name: str):
    """Top-``n_hot`` globally most frequent join-key values across the
    mesh (≙ the HYBRID_HASH skew sampler feeding
    ObSliceIdxCalc::HYBRID_HASH_*, src/sql/engine/px/ob_slice_calc.h).

    Per shard: sort keys, run-length count, local top-k; all_gather the
    candidates; re-merge and re-top-k.  Static shapes throughout.
    -> (int64[<=n_hot] hot values (_HOT_SENTINEL-padded), combined key
    per row, live mask) — key/mask returned so callers don't recompute
    the combined key for classification."""
    from oceanbase_tpu.exec.ops import _combined_key

    cols = [eval_expr(e, rel) for e in keys]
    k, _ = _combined_key(cols)
    m = rel.mask_or_true()
    n = rel.capacity

    def topk_counts(vals, cnts, k_out):
        # merge duplicate values: sort, segment-sum counts per run
        k_out = min(k_out, int(vals.shape[0]))  # top_k needs k <= len
        order = jnp.argsort(vals)
        sv = jnp.take(vals, order)
        sc = jnp.take(cnts, order)
        nn = sv.shape[0]
        newv = jnp.concatenate([jnp.ones(1, jnp.bool_),
                                sv[1:] != sv[:-1]])
        gid = jnp.cumsum(newv.astype(jnp.int64)) - 1
        tot = jax.ops.segment_sum(sc, gid, num_segments=nn)
        val = jax.ops.segment_max(sv, gid, num_segments=nn)
        tot = jnp.where(val == _HOT_SENTINEL, 0, tot)
        top_c, top_i = jax.lax.top_k(tot, k_out)
        return jnp.where(top_c > 0, jnp.take(val, top_i),
                         _HOT_SENTINEL), top_c

    ks = jnp.where(m, k, _HOT_SENTINEL)
    local_v, local_c = topk_counts(ks, jnp.ones(n, jnp.int64), n_hot)
    gv = jax.lax.all_gather(local_v, axis_name, axis=0, tiled=True)
    gc = jax.lax.all_gather(local_c, axis_name, axis=0, tiled=True)
    hot_v, _hot_c = topk_counts(gv, gc, n_hot)
    return hot_v, k, m


def dist_join_shard_hybrid(
    left: Relation,
    right: Relation,
    left_keys: Sequence[ir.Expr],
    right_keys: Sequence[ir.Expr],
    ndev: int,
    cap_per_dest: int,
    out_capacity: int,
    how: str = "inner",
    axis_name: str = PX_AXIS,
    probe_cap_per_dest: int | None = None,
    n_hot: int = 8,
):
    """Skew-resistant HASH-HASH join (≙ HYBRID_HASH_{BROADCAST,RANDOM}):

    - hot join-key values (global top-``n_hot`` of BOTH sides) are
      exempt from the hash exchange: hot BUILD rows broadcast to every
      shard, hot PROBE rows stay on their home shard — a dominant key
      never funnels into one destination's static buffer;
    - cold rows hash-repartition exactly as the plain HASH-HASH path.

    Classification is by combined key value, identical on both sides, so
    hot and cold match sets stay disjoint and the union join is exact.
    Probe-preserving joins (left/semi/anti) remain correct: each probe
    row lives on exactly one shard.  ``full`` must not use this path
    (broadcast build rows would emit unmatched copies per shard).
    """
    from oceanbase_tpu.exec.ops import compact, concat, join

    assert how != "full", "hybrid path cannot preserve a broadcast build"
    hot_l, lk, lm = _global_hot_keys(left, left_keys, n_hot, axis_name)
    hot_r, rk, rm = _global_hot_keys(right, right_keys, n_hot, axis_name)
    hotset = jnp.concatenate([hot_l, hot_r])

    def classify(k, m):
        return jnp.any(k[:, None] == hotset[None, :], axis=1) & m

    l_hot = classify(lk, lm)
    r_hot = classify(rk, rm)

    def hash_dest(k, m, is_hot):
        from oceanbase_tpu.share.keyhash import dest_of

        return jnp.where(m & ~is_hot, dest_of(k, ndev), ndev)  # hot/dead

    l_cap = (probe_cap_per_dest if probe_cap_per_dest is not None
             else cap_per_dest)
    lrecv, lov = exchange_by_dest(left, hash_dest(lk, lm, l_hot), ndev,
                                  l_cap, axis_name, kind="hash")
    rrecv, rov = exchange_by_dest(right, hash_dest(rk, rm, r_hot), ndev,
                                  cap_per_dest, axis_name, kind="hash")
    # hot probe rows stay home; hot build rows compact + broadcast.
    # The hot-build budget is a FRACTION of a destination bucket: hot
    # rows span at most 2*n_hot distinct keys, and a small static buffer
    # keeps the appended broadcast from doubling every unskewed join's
    # build capacity (overflow feeds the session retry ladder, which
    # scales cap_per_dest and this budget with it)
    hot_cap = max(cap_per_dest // 8, 512)
    local_hot_probe = left.with_mask(l_hot)
    hot_build_local = compact(right.with_mask(r_hot), capacity=hot_cap)
    hot_overflow = jnp.maximum(
        jnp.sum(r_hot.astype(jnp.int64)) - hot_cap, 0)
    hot_build = broadcast_gather(hot_build_local, axis_name, kind="hash")

    probe_all = concat([lrecv, local_hot_probe])
    build_all = concat([rrecv, hot_build])
    out = join(probe_all, build_all, left_keys, right_keys, how=how,
               out_capacity=out_capacity)
    return out, lov + rov + hot_overflow


def dist_join_shard(
    left: Relation,
    right: Relation,
    left_keys: Sequence[ir.Expr],
    right_keys: Sequence[ir.Expr],
    ndev: int,
    cap_per_dest: int,
    out_capacity: int,
    how: str = "inner",
    axis_name: str = PX_AXIS,
    probe_cap_per_dest: int | None = None,
):
    """HASH-HASH distributed join: repartition both inputs on the join key
    so matching keys co-locate, then local sort-join per chip
    (≙ PX HASH dist join, ObSliceIdxCalc::SliceCalcType HASH both sides).

    ``probe_cap_per_dest`` lets a runtime join filter budget the probe
    exchange below the build exchange (bloom-filtered probes carry far
    fewer live rows).

    Returns (relation, global overflow count); see dist_groupby_shard."""
    from oceanbase_tpu.exec.ops import join

    lrecv, lov = all_to_all_repartition(
        left, left_keys, ndev,
        probe_cap_per_dest if probe_cap_per_dest is not None
        else cap_per_dest, axis_name, kind="hash")
    rrecv, rov = all_to_all_repartition(right, right_keys, ndev, cap_per_dest,
                                        axis_name, kind="hash")
    out = join(lrecv, rrecv, left_keys, right_keys, how=how,
               out_capacity=out_capacity)
    return out, lov + rov  # LOCAL count; callers psum as needed
