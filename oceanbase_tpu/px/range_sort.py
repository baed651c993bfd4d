"""Distributed ORDER BY: sampled RANGE repartition + shard-local sort.

Reference analog: the RANGE slice strategy fed by the range-distribution
datahub (samples negotiated through the QC —
src/sql/engine/px/ob_slice_calc.h RANGE,
src/sql/engine/px/datahub/components/ob_dh_range_dist_wf.h).  On TPU the
"datahub round trip" is an all_gather of per-shard samples: every shard
derives the SAME splitters, ships rows by searchsorted(splitters, key),
and sorts its slice locally.  Gathering shards in mesh order then yields
a globally sorted relation — the coordinator never sorts anything
(round-1's gather-then-sort bottleneck, VERDICT Weak #5).

Equal first-key values always map to one destination (dest is a pure
function of the key value), so multi-key sorts stay correct: the shard
holding a first-key run lexsorts it by the remaining keys locally.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from oceanbase_tpu.exec.ops import sort_rows
from oceanbase_tpu.expr import ir
from oceanbase_tpu.expr.compile import eval_expr
from oceanbase_tpu.px.exchange import PX_AXIS, exchange_by_dest
from oceanbase_tpu.vector.column import Relation

SAMPLES_PER_SHARD = 64


def _primary_scalar(rel: Relation, key: ir.Expr, asc: bool):
    """First sort key -> one monotonically ordered scalar per row, with
    MySQL NULL placement (NULL smallest) and DESC folded in by negation.
    String columns order by their dictionary codes (order-preserving)."""
    c = eval_expr(key, rel)
    d = c.data
    if d.dtype == jnp.bool_:
        d = d.astype(jnp.int32)
    if jnp.issubdtype(d.dtype, jnp.floating):
        d = d.astype(jnp.float64)
        if not asc:
            d = -d
        # the local comparator (jnp.lexsort) always orders NaN LAST, for
        # ASC and DESC alike — the range dest must agree, so NaN maps to
        # +inf AFTER the DESC negation
        d = jnp.where(jnp.isnan(d), jnp.inf, d)
        if c.valid is not None:
            # NULL sorts smallest: first under ASC (-inf), last under
            # DESC (+inf after negation)
            nullv = -jnp.inf if asc else jnp.inf
            d = jnp.where(c.valid, d, nullv)
        return d
    d = d.astype(jnp.int64)
    if not asc:
        d = -d
    if c.valid is not None:
        lo = jnp.iinfo(jnp.int64).min
        hi = jnp.iinfo(jnp.int64).max
        d = jnp.where(c.valid, d, lo if asc else hi)
    return d


def _splitters(prim, live, ndev: int, axis_name: str):
    """Per-shard strided sample -> all_gather -> identical splitters on
    every shard (the datahub negotiation as one collective)."""
    n = prim.shape[0]
    k = min(SAMPLES_PER_SHARD, n)
    stride = max(n // k, 1)
    idx = jnp.arange(k) * stride
    sv = jnp.take(prim, idx)
    sl = jnp.take(live, idx)
    # dead samples sort to the top and are excluded by live-count math
    if jnp.issubdtype(prim.dtype, jnp.floating):
        dead = jnp.inf
    else:
        dead = jnp.iinfo(jnp.int64).max
    sv = jnp.where(sl, sv, dead)
    allv = jax.lax.all_gather(sv, axis_name, axis=0, tiled=True)
    alll = jax.lax.all_gather(sl, axis_name, axis=0, tiled=True)
    allv = jnp.sort(allv)
    total_live = jnp.sum(alll.astype(jnp.int64))
    # quantile positions among the live (sorted-first) samples
    pos = (jnp.arange(1, ndev) * total_live) // ndev
    return jnp.take(allv, jnp.clip(pos, 0, allv.shape[0] - 1))


def dist_sort_shard(
    rel: Relation,
    keys: Sequence[ir.Expr],
    ascending: Sequence[bool] | None,
    ndev: int,
    cap_per_dest: int,
    axis_name: str = PX_AXIS,
):
    """Per-shard body (inside shard_map): range-exchange by the first
    sort key, then full local lexsort.  After gathering shards in mesh
    order the relation is globally sorted (dead rows interleave at each
    shard's tail; downstream limit/materialize are mask-aware).

    Returns (locally sorted slice, local overflow count)."""
    if ascending is None:
        ascending = [True] * len(keys)
    m = rel.mask_or_true()
    prim = _primary_scalar(rel, keys[0], ascending[0])
    spl = _splitters(prim, m, ndev, axis_name)
    dest = jnp.searchsorted(spl, prim, side="right").astype(jnp.int32)
    dest = jnp.where(m, dest, ndev)
    recv, ovf = exchange_by_dest(rel, dest, ndev, cap_per_dest, axis_name,
                                 kind="sort")
    return sort_rows(recv, keys, ascending), ovf
