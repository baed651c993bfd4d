"""Core vectorized operators (filter/project/group-by/join/sort/limit).

Design notes (tpu-first re-imaginations of the reference components):

- ``filter_rows``    ≙ ObOperator filter_rows + skip bitmap accounting
  (src/sql/engine/ob_operator.cpp:1466-1560): produces a mask, never copies.
- ``hash_groupby``   ≙ ObHashGroupByVecOp + ObExtendHashTableVec
  (src/sql/engine/aggregate/ob_hash_groupby_vec_op.cpp,
  src/sql/engine/aggregate/ob_exec_hash_struct_vec.h).  On TPU a dynamic
  hash table is hostile to XLA, so grouping is *sort-based*: lexsort on the
  key columns, segment boundaries, segment reductions — O(n log n) on the
  sort network but fully fused, static-shaped, MXU/VPU friendly.
- ``join``           ≙ ObHashJoinVecOp build/probe
  (src/sql/engine/join/hash_join/ob_hash_join_vec_op.h:342).  Implemented as
  sort + rank: build side is sorted by key; every probe row gets the range
  of equal build keys (``_probe_ranges``: one sort of build and probe keys
  together and streaming scans, since random gathers are what the TPU does
  worst; a binary search only where the probe is too small to pay for a
  sort's compile); expansion
  to a static output capacity via jnp.repeat(total_repeat_length=...);
  multi-column keys go through a 64-bit mix with exact-key verification
  (false positives masked, ≙ the reference's normalized-key fast path in
  join_hash_table.h:16 with key re-check).  A build side the planner
  found unique on the key (a declared primary key, ``build_unique``)
  needs none of the expansion: ``_join_on_probe_lanes`` pairs each probe
  lane with at most one build row and emits on the probe's lanes.
- ``sort_rows``      ≙ ObSortVecOp (src/sql/engine/sort/ob_sort_vec_op.h:62).
- Aggregate null/valid handling ≙ IAggregate::add_batch_rows
  (src/share/aggregate/agg_ctx.h:552): dead/null lanes contribute the
  aggregate's identity element instead of branching.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from oceanbase_tpu.datatypes import SqlType, TypeKind
from oceanbase_tpu.exec import diag
from oceanbase_tpu.expr import ir
from oceanbase_tpu.expr.compile import cast_column, eval_expr, eval_predicate
from oceanbase_tpu.share import keyhash
from oceanbase_tpu.share.keyhash import mix64 as _mix64
from oceanbase_tpu.vector.column import (SCAN_ROW, Column, Relation,
                                          StringDict, prefix_sum)

# ---------------------------------------------------------------------------
# basics
# ---------------------------------------------------------------------------


def filter_rows(rel: Relation, pred: ir.Expr) -> Relation:
    return rel.with_mask(eval_predicate(pred, rel))


def project(rel: Relation, outputs: dict[str, ir.Expr]) -> Relation:
    cols = {name: eval_expr(e, rel) for name, e in outputs.items()}
    return Relation(columns=cols, mask=rel.mask)


def top_n(rel: Relation, key: ir.Expr, ascending: bool, k: int) -> Relation:
    """Fused ORDER BY <single key> LIMIT k via lax.top_k (≙ top-N sort
    pushdown, ob_sort_vec_op top-n path).  Result rows arrive in sort
    order; ties may order differently from the stable full sort."""

    n = rel.capacity
    m = rel.mask_or_true()
    c = eval_expr(key, rel)
    d = c.data
    if jnp.issubdtype(d.dtype, jnp.floating):
        score = jnp.where(jnp.isnan(d), -jnp.inf, d)
        score = -score if ascending else score
        big = jnp.asarray(jnp.inf, score.dtype)
        null_last = jnp.asarray(jnp.finfo(score.dtype).min, score.dtype)
    else:
        score = (-d.astype(jnp.int64)) if ascending else d.astype(jnp.int64)
        big = jnp.asarray(_INT_MAX, jnp.int64)
        null_last = -big + 1
    if c.valid is not None:
        # MySQL: NULL sorts smallest -> first under ASC, last under DESC;
        # a live NULL must still outrank dead (masked) rows, so its
        # sentinel sits strictly above the dead sentinel
        score = jnp.where(c.valid, score, big if ascending else null_last)
    score = jnp.where(m, score, -big)  # dead rows always lose
    _vals, idx = lax.top_k(score, min(k, n))
    out = rel.gather(idx, mask=jnp.take(m, idx))
    return out


def limit(rel: Relation, k: int, offset: int = 0) -> Relation:
    m = rel.mask_or_true()
    rank = jnp.cumsum(m.astype(jnp.int64)) - 1  # rank among live rows
    keep = m & (rank >= offset) & (rank < offset + k)
    return rel.with_mask(keep)


def compact(rel: Relation, capacity: int | None = None,
            strict: bool = False) -> Relation:
    """Densify live rows to the front (stable).  Used before exchanges and
    as a cardinality-reduction point after selective filters/group-bys —
    the analog of the reference compacting batches when skip ratio is high
    (ObBatchRows all_rows_active_).

    ``strict`` reports rows that do not fit ``capacity`` on the
    ``compact_overflow`` diagnostic lane instead of silently truncating —
    required wherever Compact feeds an aggregate (dropped rows there are
    wrong answers, not wasted lanes) so the executor retries with scaled
    budgets."""
    n = rel.capacity
    cap = capacity if capacity is not None else n
    m = rel.mask_or_true()
    if strict and capacity is not None:
        live_n = jnp.sum(m.astype(jnp.int64))
        diag.push("compact_overflow", jnp.maximum(live_n - cap, 0),
                  capacity=cap)
    order = _lexsort((~m,))  # live rows first, stable
    idx = order[:cap]
    live = jnp.take(m, idx)
    out = rel.gather(idx, mask=live)
    return out


# ---------------------------------------------------------------------------
# sort
# ---------------------------------------------------------------------------


def _sort_with_rows(keys: Sequence[jax.Array],
                    payloads: Sequence[jax.Array] = ()
                    ) -> tuple[jax.Array, ...]:
    """The keys sorted (last key primary, returned first), then the int32
    row numbers in that order, then each of ``payloads`` moved as the
    rows moved: an UNSTABLE sort that takes the row number as its last
    key.  Ties break exactly as the stable sort breaks them, so the
    answer is the same, and the TPU compiler takes about half as long
    over it: a stable lexsort of three int64 keys at 1M rows compiled
    for a v5e in 448 s, this form in 243 s; a one-key argsort in 106 s
    against 43 s (PR 22).

    What a caller then reads in sorted order it takes from here and not
    by a gather through the row numbers: the sorted keys cost nothing
    (they are the sort's own outputs), a payload about 1-1.5 ns a lane
    and 32-bit word, a gather 17-28 ns an element at every lane count
    the records hold (PR 45, PERF.md section 6)."""
    iota = lax.iota(jnp.int32, keys[0].shape[0])
    return lax.sort((*reversed(tuple(keys)), iota, *payloads),
                    num_keys=len(keys) + 1, is_stable=False)


def _lexsort(keys: Sequence[jax.Array]) -> jax.Array:
    """``jnp.lexsort``'s permutation (last key primary): the row numbers
    of ``_sort_with_rows`` alone, for a caller that reads whole relations
    through them (``compact``, ``sort_rows``).  The sorted keys are
    dropped here; a caller that wants them calls ``_sort_with_rows``."""
    return _sort_with_rows(keys)[-1].astype(jnp.int64)


def _sort_key_arrays(rel: Relation, keys: Sequence[ir.Expr],
                     ascending: Sequence[bool],
                     nulls_first: Sequence[bool] | None = None):
    """Build lexsort key arrays (minor..major order for jnp.lexsort).

    MySQL semantics: NULL sorts as the smallest value — first under ASC,
    last under DESC; ``nulls_first`` overrides per key (NULLS FIRST/LAST).
    """
    m = rel.mask_or_true()
    arrs = []
    for i, (e, asc) in enumerate(zip(keys, ascending)):
        c = eval_expr(e, rel)
        d = c.data
        if d.dtype == jnp.bool_:
            d = d.astype(jnp.int32)
        if not asc:
            if jnp.issubdtype(d.dtype, jnp.floating):
                d = -d
            else:
                d = -d.astype(jnp.int64)
        if c.valid is not None:
            nf = nulls_first[i] if nulls_first is not None else asc
            nk = jnp.where(c.valid, 0, -1 if nf else 1).astype(jnp.int8)
            arrs.append((nk, d))
        else:
            arrs.append((None, d))
    minor_to_major = []
    for nk, d in reversed(arrs):
        minor_to_major.append(d)
        if nk is not None:
            minor_to_major.append(nk)
    # dead rows always last (most-major key)
    minor_to_major.append((~m).astype(jnp.int8))
    return minor_to_major, m


def sort_rows(rel: Relation, keys: Sequence[ir.Expr],
              ascending: Sequence[bool] | None = None,
              nulls_first: Sequence[bool] | None = None) -> Relation:
    if ascending is None:
        ascending = [True] * len(keys)
    karrs, m = _sort_key_arrays(rel, keys, ascending, nulls_first)
    order = _lexsort(karrs)
    live = jnp.take(m, order)
    return rel.gather(order, mask=live)


# ---------------------------------------------------------------------------
# aggregation
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AggSpec:
    """One aggregate output: name -> fn(arg)."""

    name: str
    fn: str  # sum | count | count_star | min | max | avg | count_distinct
    arg: Optional[ir.Expr] = None


_INT_MIN = np.iinfo(np.int64).min
_INT32_MIN = np.iinfo(np.int32).min
_INT_MAX = np.iinfo(np.int64).max


def _agg_identity(fn: str, dtype):
    if fn in ("sum", "count", "count_star", "avg"):
        return jnp.asarray(0, dtype=dtype)
    if fn == "min":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(jnp.inf, dtype=dtype)
        return jnp.asarray(np.iinfo(np.dtype(dtype)).max, dtype=dtype)
    if fn == "max":
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(-jnp.inf, dtype=dtype)
        return jnp.asarray(np.iinfo(np.dtype(dtype)).min, dtype=dtype)
    raise ValueError(fn)


def _agg_result_type(fn: str, argt: SqlType | None) -> SqlType:
    if fn in ("count", "count_star", "count_distinct"):
        return SqlType.int_()
    if fn == "avg":
        return SqlType.double()
    assert argt is not None
    if fn == "sum" and argt.kind == TypeKind.BOOL:
        return SqlType.int_()
    return argt


def _scoped_segment_reduce(fn):
    """The group-by's reductions over its sorted lanes under one scope
    name, so a profile reads "GroupBy#k/groupby.segment_reduce" (HLO
    metadata only)."""
    @functools.wraps(fn)
    def scoped(*args, **kw):
        with jax.named_scope("groupby.segment_reduce"):
            return fn(*args, **kw)
    return scoped


_SCAN_OPS = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}


def _segmented_scan(fn: str, d: jax.Array, head: jax.Array) -> jax.Array:
    """The running ``fn`` (sum | min | max) of ``d`` that starts anew on
    every lane where ``head`` is set: on the last lane of a run it is the
    run's aggregate.  In two levels, as ``prefix_sum``: within rows of
    ``SCAN_ROW`` lanes by doubling shifts (ten steps of elementwise
    passes; a value and the flag "a head lies in what I cover"), then the
    rows' last values by the same scan over the rows.  For what a
    difference of prefixes cannot give: a minimum, a maximum, and a
    floating-point sum (a difference of two long prefixes cancels)."""
    n = d.shape[0]
    if n == 0:
        return d
    op, ident = _SCAN_OPS[fn], _agg_identity(fn, d.dtype)
    width = min(n, SCAN_ROW)
    v = jnp.pad(d, (0, -n % width), constant_values=ident)
    v = v.reshape(-1, width)
    f = jnp.pad(head, (0, -n % width)).reshape(-1, width)
    rows = v.shape[0]
    k = 1
    while k < width:
        before = jnp.concatenate(
            [jnp.full((rows, k), ident), v[:, :-k]], axis=1)
        v = jnp.where(f, v, op(before, v))
        f = f | jnp.concatenate(
            [jnp.zeros((rows, k), jnp.bool_), f[:, :-k]], axis=1)
        k *= 2
    if rows > 1:
        through = _segmented_scan(fn, v[:, -1], f[:, -1])
        before = jnp.concatenate([ident[None], through[:-1]])
        v = jnp.where(f, v, op(before[:, None], v))
    return v.reshape(-1)[:n]


def _since_previous(x: jax.Array, first) -> jax.Array:
    """``x[j] - x[j - 1]``, ``x[0] - first`` on lane 0 (wrapping)."""
    return x - jnp.concatenate([jnp.full((1,), first, x.dtype), x])[:-1]


class _SortedGroups:
    """The groups of lanes that lie in group order (live lanes first, a
    group's lanes together; ``newgrp`` marks the lane that starts one),
    and their reductions WITHOUT a scatter: the lane that ends each group
    is brought to the front, in group order, by ONE sort of a single
    int32 key (the lane number, made negative on a lane that ends a
    group); a per-group value is then read at those ``cap`` lanes.  A
    sum or a count is the difference of a prefix sum between one group's
    end and the previous group's (wrapping integer arithmetic: bit-equal
    to a scatter's sum whatever wraps on the way); a minimum, a maximum
    and a floating-point sum are a segmented scan's value at the end.

    Measured on a v5e (PR 43, PERF.md section 6): ``jax.ops.segment_*``
    over ``gid`` ran 70-88 ns a lane and a reduction, this a few ns a
    lane for the sort and the scans plus a gather of ``cap`` lanes a
    32-bit word.  The prefixes do not ride THIS sort as operands: every
    32-bit operand costs the TPU compiler 6-8 s more (a sort of the key
    alone 3 s, with one int64 operand 18 s, at 524,288 lanes), an
    aggregate more would be seconds of every first run more, for the
    ``cap``-lane gathers it saves (31 ms of Q3's 484).  The sort BEFORE
    this one, which puts the lanes in group order, is where operands
    pay: it carries the keys anyway and each aggregate's argument as a
    payload (``hash_groupby``), which spares gathers over all ``n``
    lanes, not ``cap``."""

    def __init__(self, s_live: jax.Array, newgrp: jax.Array, cap: int):
        n = s_live.shape[0]
        self.newgrp = newgrp
        self.n_groups = jnp.sum(newgrp, dtype=jnp.int64)
        self.mask = jnp.arange(cap) < self.n_groups
        # dead lanes lie last: a live lane ends its group when the next
        # lane starts one or is dead
        ends = s_live & jnp.concatenate(
            [newgrp[1:] | ~s_live[1:], jnp.ones(1, jnp.bool_)])[:n]
        lane = lax.iota(jnp.int32, n)
        (key,) = lax.sort((jnp.where(ends, lane + _INT32_MIN, lane),),
                          num_keys=1, is_stable=False)
        # (lanes past the last group all read lane 0)
        self.at = jnp.where(self.mask, key[:cap] - _INT32_MIN, 0)
        diag.note("groupby_reduce", "scan")

    def _of(self, x: jax.Array) -> jax.Array:
        return jnp.where(self.mask, x, jnp.zeros((), x.dtype))

    @functools.cached_property
    def sizes(self) -> jax.Array:
        """Lanes a group (all live: ``count(*)``)."""
        return self._of(_since_previous(self.at, -1)).astype(jnp.int64)

    @_scoped_segment_reduce
    def total(self, d: jax.Array) -> jax.Array:
        """The integer sum of ``d`` a group (0 on the lanes that do not
        count, dead lanes included)."""
        diag.note("groupby_reduce", "scan")
        return self._of(_since_previous(
            jnp.take(prefix_sum(d), self.at), 0))

    @_scoped_segment_reduce
    def scanned(self, fn: str, d: jax.Array) -> jax.Array:
        """``fn`` (sum | min | max) of ``d`` a group (``fn``'s identity on
        the lanes that do not count)."""
        diag.note("groupby_reduce", "scan")
        return self._of(jnp.take(_segmented_scan(fn, d, self.newgrp),
                                 self.at))


LOWCARD_GROUP_LIMIT = 4096

# lanes a block of the masked reduce: partials per block, then over the
# blocks (PR 30, TPU v5e: 0.28 ms a sum of 8,388,608 lanes into 7 segments
# where one reduce over all the lanes takes 0.86; the same from 1,024 to
# 65,536 lanes a block, and this one compiles fastest)
_MASKED_REDUCE_BLOCK = 1 << 16

_MASKED_REDUCES = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}


def _lowcard_reduce(fn: str, d: jax.Array, gid: jax.Array,
                    nseg: int) -> jax.Array:
    """``fn`` (sum | min | max) over the lanes of ``d`` for each of the
    segments ``0 .. nseg - 2``; the last segment is the dead lanes' and is
    not returned.  The caller has put ``fn``'s identity into the lanes
    that do not count.

    For every segment ``g``, ``fn(where(gid == g, d, identity))`` over the
    lanes, block by block and then over the blocks' partials.  The compare
    against the segment numbers is broadcast inside the reduce's fusion
    and nothing of ``(segments, lanes)`` is written: streaming compare,
    select, reduce, with no scatter.  The cost is lanes x segments (PR 30,
    TPU v5e, one int64 sum: 0.03 ns a lane at 7 segments, 1.2 at 512, 8.9
    at 4,097, the most ``LOWCARD_GROUP_LIMIT`` admits), where the scatter
    of ``jax.ops.segment_sum`` took 60-80 ns a lane at any of them.

    Integer results are bit-equal to a scatter's (a wrapping int64 sum
    does not depend on the order); float sums differ by summation order.
    """
    n = d.shape[0]
    k = _MASKED_REDUCE_BLOCK if n % _MASKED_REDUCE_BLOCK == 0 else n
    ident = _agg_identity(fn, d.dtype)
    # (``initial``: a relation of no lanes has no block to reduce)
    reduce = functools.partial(_MASKED_REDUCES[fn], initial=ident)
    with jax.named_scope("groupby.masked_reduce"):
        hit = (gid.reshape(1, n // k, k)
               == lax.iota(gid.dtype, nseg - 1)[:, None, None])
        partial = reduce(jnp.where(hit, d.reshape(1, n // k, k), ident),
                         axis=2)
        return reduce(partial, axis=1)


# An aggregate's argument rides the group-by's sort as a payload when the
# gathers it spares (``n`` lanes x the argument's 32-bit operands) reach
# the first constant AND the sort stays within the second.  On a TPU v5e
# (PR 45, PERF.md section 6; the group-by alone, argument gathered |
# riding): at 67,108,864 lanes and one int64 key 3.898 | 2.132 s an
# execution for 22.7 s more of backend compile (6 operands); at
# 33,554,432 lanes with a nullable argument 0.663 | 0.347 s for 22.1 s
# (7); at 524,288 lanes and three keys 0.0692 | 0.0567 s for 35.6 s (8
# operands: 28 s more of TPC-H Q3's 146 s program for 12 ms of its 462):
# under 16.8M reads a program keeps its compile time (a read is 13-22 ns,
# so 0.2-0.4 s an execution at the constant).  What a sort costs the
# compiler grows faster than its operands: Q18's five-key group-by over
# 8,388,608 lanes (10 operands) compiled for a described v5e in the
# sandbox in 612 s, and in 2,118 s with the int64 payload as its 11th and
# 12th, which alone takes the cell's first run past its limit.
_RIDE_MIN_READS = 1 << 24
_RIDE_MAX_SORT_OPERANDS = 8


def _sort_operands(*arrays) -> int:
    """32-bit operands the TPU sorts ``arrays`` as (a 64-bit array is two,
    anything narrower one; ``None`` none)."""
    return sum(max(a.dtype.itemsize // 4, 1) for a in arrays
               if a is not None)


def _rides_sort(n: int, sort_operands: int, words: int) -> bool:
    """The shape rule: does an argument of ``words`` 32-bit operands (its
    validity one of them) of a group-by over ``n`` lanes ride the sort
    that already has ``sort_operands`` (or is it gathered through the
    sort's row numbers)?"""
    return (n * words >= _RIDE_MIN_READS
            and sort_operands + words <= _RIDE_MAX_SORT_OPERANDS)


def hash_groupby(
    rel: Relation,
    group_by: dict[str, ir.Expr],
    aggs: Sequence[AggSpec],
    out_capacity: int | None = None,
    return_overflow: bool = False,
):
    """Vectorized GROUP BY via sort + segment reduce (the reductions over
    the sorted lanes are scans read at each group's last lane, with no
    scatter: ``_SortedGroups``).

    Two sorts, and what each carries.  The FIRST (``_sort_with_rows``)
    orders the lanes: its keys are the dead-lane flag, each group key
    (with its validity) and the row number, and what the group-by reads
    in sorted order are its outputs, not gathers through its row
    numbers: the live flag and the keys ARE the sorted keys (no operand
    more, no compile time more), and each distinct aggregate argument
    (``sum(x)`` and ``avg(x)`` share one; an argument that is a group
    key needs none) rides behind the row number as a payload operand
    where ``_rides_sort`` finds the lanes many enough and the sort
    narrow enough for what an operand costs the compiler, and is
    gathered through the row numbers where not.
    On a v5e a gather over ``n`` lanes costs 17-28 ns an element and
    word, a sort operand 1-1.5 ns (PR 45, PERF.md section 6: Q18's
    subquery group-by over 67,108,864 lanes read five words through the
    permutation in 7.35 s beside a sort of 0.36 s).  The SECOND
    (``_SortedGroups``) brings the groups' end lanes to the front and
    stays key-only.  ``count(distinct)`` keeps its own re-sort and
    gathers.

    Fast path: when every group key is dictionary-encoded (or bool) and
    the code-space product is small, the group id IS the combined code —
    no sort at all, and a static segment count (the dictionary makes
    cardinality a compile-time fact; ≙ the reference's groupby pushdown
    on dict-encoded columns, ob_cg_group_by_scanner).  Each aggregate is
    then one streaming pass of masked reductions, one per segment, with
    no scatter (``_lowcard_reduce``).  Q1's 6-group aggregate over 6M
    rows skips the 6M-row lexsort entirely.

    Output relation: one row per group, capacity = min(n, out_capacity),
    mask marks real groups.  With no group keys use scalar_agg instead.
    """
    n = rel.capacity
    m = rel.mask_or_true()

    fast = _lowcard_groupby(rel, group_by, aggs, out_capacity, n, m)
    if fast is not None:
        if return_overflow:
            return fast, jnp.zeros((), dtype=jnp.int64)
        return fast

    diag.note("groupby", "sort")
    diag.note("groupby_sort_lanes", "", n)
    key_cols = {name: eval_expr(e, rel) for name, e in group_by.items()}
    # canonicalize NULL payloads so all NULLs of a key share one group
    # (GROUP BY treats NULLs as equal; the validity lane separates them
    # from real zeros in both the sort and the boundary check)
    for name, c in list(key_cols.items()):
        if c.valid is not None:
            key_cols[name] = c.with_data(
                jnp.where(c.valid, c.data, jnp.zeros((), c.data.dtype))
            )

    # what the aggregates read in sorted order: one column a distinct
    # argument (``sum(x)`` and ``avg(x)`` share one), none for an
    # argument that is a group key
    key_of = {ir.structural_key(e): name for name, e in group_by.items()}
    arg_cols: dict = {}
    for spec in aggs:
        if spec.fn in ("count_star", "count_distinct"):
            continue
        assert spec.arg is not None
        k = ir.structural_key(spec.arg)
        if k not in key_of and k not in arg_cols:
            arg_cols[k] = eval_expr(spec.arg, rel)

    # sort: dead rows last, then lexicographic group keys (nulls are a
    # group); an argument rides it as a payload behind the row number
    # where the shape rule says so, and is gathered where not
    minor_to_major = []
    for name in reversed(list(key_cols)):
        c = key_cols[name]
        d = c.data.astype(jnp.int64) if c.data.dtype == jnp.bool_ else c.data
        minor_to_major.append(d)
        if c.valid is not None:
            minor_to_major.append((~c.valid).astype(jnp.int8))
    minor_to_major.append((~m).astype(jnp.int8))
    width = _sort_operands(*minor_to_major) + 1   # (and the row number)
    riders, payloads = set(), []
    for k, ac in arg_cols.items():
        words = _sort_operands(ac.data, ac.valid)
        if _rides_sort(n, width, words):
            riders.add(k)
            width += words
            payloads.append(ac.data)
            if ac.valid is not None:
                payloads.append(ac.valid.astype(jnp.int8))
    # (major key first, as the sort returns them)
    moved = iter(_sort_with_rows(minor_to_major, payloads))

    # the sorted lanes are the sort's own outputs: the same values a
    # gather through its row numbers reads, lane for lane
    s_live = next(moved) == 0
    diag.note("groupby_sorted_read", "sort")
    s_keys = {}
    for name, c in key_cols.items():
        s_valid = (next(moved) == 0) if c.valid is not None else None
        s_keys[name] = c.with_data(next(moved).astype(c.data.dtype), s_valid)
    order = next(moved)
    s_args = {}
    for k, ac in arg_cols.items():
        if k in riders:
            diag.note("groupby_sorted_read", "sort")
            s_data = next(moved)
            s_args[k] = ac.with_data(
                s_data, (next(moved) != 0) if ac.valid is not None else None)
        else:
            diag.note("groupby_sorted_read", "gather")
            s_args[k] = ac.gather(order)
    for k, name in key_of.items():
        s_args[k] = s_keys[name]

    # new-group boundary among live rows
    diff = jnp.zeros(n, dtype=jnp.bool_)
    for c in s_keys.values():
        d = c.data
        dneq = jnp.concatenate([jnp.ones(1, jnp.bool_), d[1:] != d[:-1]])
        if c.valid is not None:
            v = c.valid
            vneq = jnp.concatenate([jnp.ones(1, jnp.bool_), v[1:] != v[:-1]])
            dneq = dneq | vneq
            # equal codes but both NULL -> same group: handled since value
            # lanes are compared raw; NULL payloads share the stored data
        diff = diff | dneq
    if not key_cols:
        diff = jnp.concatenate([jnp.ones(1, jnp.bool_), jnp.zeros(n - 1, jnp.bool_)])
    newgrp = diff & s_live

    cap = min(out_capacity, n) if out_capacity is not None else n
    groups = _SortedGroups(s_live, newgrp, cap)
    diag.note("groupby_out_lanes", "", cap)
    diag.count_rows(diag.GROUPS, 0, groups.n_groups)
    # groups beyond capacity would vanish silently — surface it (diag when
    # lowered via execute_plan, explicit lane for shard_map callers)
    gb_overflow = jnp.maximum(groups.n_groups - cap, 0)
    diag.push("groupby_overflow", gb_overflow, capacity=cap)

    # a group's key values: those of the lane that ends it
    out_cols: dict[str, Column] = {}
    out_mask = groups.mask
    for name, c in s_keys.items():
        out_cols[name] = c.gather(groups.at)

    # aggregate lanes (evaluated pre-sort, moved by the sort)
    for spec in aggs:
        if spec.fn == "count_star":
            out_cols[spec.name] = Column(groups.sizes, None, SqlType.int_())
            continue
        if spec.fn == "count_distinct":
            diag.note("groupby_reduce", "scatter")
            diag.note("groupby_sorted_read", "gather")
            res = _count_distinct(minor_to_major, key_cols, rel, spec,
                                  n)[:cap]
            out_cols[spec.name] = Column(res, None, SqlType.int_())
            continue
        ac = s_args[ir.structural_key(spec.arg)]
        if ac.dtype.kind == TypeKind.BOOL:
            ac = cast_column(ac, SqlType.int_())
        s_data, s_valid = ac.data, ac.valid
        # the lanes that count, and how many a group has of them
        if s_valid is None:
            weight, cnt = s_live, groups.sizes
        else:
            weight = s_live & s_valid
            cnt = groups.total(weight.astype(jnp.int32)).astype(jnp.int64)
        if spec.fn == "count":
            out_cols[spec.name] = Column(cnt, None, SqlType.int_())
            continue
        fn = "sum" if spec.fn == "avg" else spec.fn
        d = jnp.where(weight, s_data, _agg_identity(fn, s_data.dtype))
        if fn == "sum" and not jnp.issubdtype(d.dtype, jnp.floating):
            res = groups.total(d)
        else:
            res = groups.scanned(fn, d)
        valid = cnt > 0  # SUM / MIN / MAX over an all-NULL group is NULL
        if spec.fn == "avg":
            num = res.astype(jnp.float64)
            if ac.dtype.kind == TypeKind.DECIMAL:
                num = num / (10 ** ac.dtype.scale)
            res = num / jnp.maximum(cnt, 1).astype(jnp.float64)
            out_cols[spec.name] = Column(res, valid, SqlType.double())
        else:
            out_cols[spec.name] = Column(
                res, valid, _agg_result_type(spec.fn, ac.dtype),
                sdict=ac.sdict if spec.fn in ("min", "max") else None)

    result = Relation(columns=out_cols, mask=out_mask)
    if return_overflow:
        return result, gb_overflow
    return result


def _lowcard_groupby(rel, group_by, aggs, out_capacity, n, m):
    """Direct-code group-by; None when ineligible (falls back to sort)."""
    key_cols = {}
    sizes = []
    for name, e in group_by.items():
        c = eval_expr(e, rel)
        if c.dtype.kind == TypeKind.BOOL:
            size = 2
        elif c.sdict is not None:
            size = c.sdict.size
        else:
            return None
        nullable = c.valid is not None
        key_cols[name] = (c, size, nullable)
        sizes.append(size + (1 if nullable else 0))
    if not key_cols:
        return None
    prod = 1
    for s in sizes:
        prod *= s
        if prod > LOWCARD_GROUP_LIMIT:
            return None
    if any(a.fn == "count_distinct" for a in aggs):
        return None
    if out_capacity is not None and out_capacity < prod:
        return None

    # combined group id (lexicographic in key order, so output ordering
    # matches the sort-based path: dictionary codes are order-preserving)
    # (int32: the code space is at most LOWCARD_GROUP_LIMIT, and every
    # aggregate's reduce streams the group id beside its column: 12
    # bytes a lane of an int64 column, where an int64 id makes 16)
    gid = jnp.zeros(n, dtype=jnp.int32)
    for (name, (c, size, nullable)), span in zip(key_cols.items(), sizes):
        code = c.data.astype(jnp.int32)
        if nullable:
            # NULL gets its own slot BELOW real codes (NULL sorts first)
            code = jnp.where(c.valid, code + 1, 0)
        gid = gid * span + jnp.clip(code, 0, span - 1)
    gid = jnp.where(m, gid, prod)  # dead rows -> spill slot
    nseg = prod + 1

    out_cols: dict[str, Column] = {}
    counts = _lowcard_reduce("sum", m.astype(jnp.int64), gid, nseg)
    occupied = counts > 0

    # decode group ids back into per-key code columns
    rem = jnp.arange(prod, dtype=jnp.int64)
    decoded = {}
    for (name, (c, size, nullable)), span in reversed(
            list(zip(key_cols.items(), sizes))):
        code = rem % span
        rem = rem // span
        if nullable:
            valid = code > 0
            data = jnp.clip(code - 1, 0, max(size - 1, 0))
        else:
            valid = None
            data = code
        decoded[name] = Column(data.astype(c.data.dtype), valid, c.dtype,
                               c.sdict)
    for name in key_cols:
        out_cols[name] = decoded[name]

    for spec in aggs:
        if spec.fn == "count_star":
            out_cols[spec.name] = Column(counts, None, SqlType.int_())
            continue
        ac = eval_expr(spec.arg, rel)
        if ac.dtype.kind == TypeKind.BOOL:
            ac = cast_column(ac, SqlType.int_())
        if ac.valid is None:
            weight, cnt = m, counts
        else:
            weight = m & ac.valid
            cnt = _lowcard_reduce("sum", weight.astype(jnp.int64), gid, nseg)
        if spec.fn == "count":
            out_cols[spec.name] = Column(cnt, None, SqlType.int_())
            continue
        if spec.fn in ("sum", "avg"):
            d = jnp.where(weight, ac.data, jnp.zeros((), ac.data.dtype))
            s = _lowcard_reduce("sum", d, gid, nseg)
            if spec.fn == "sum":
                out_cols[spec.name] = Column(
                    s, cnt > 0, _agg_result_type("sum", ac.dtype))
            else:
                if ac.dtype.kind == TypeKind.DECIMAL:
                    num = s.astype(jnp.float64) / (10 ** ac.dtype.scale)
                else:
                    num = s.astype(jnp.float64)
                res = num / jnp.maximum(cnt, 1).astype(jnp.float64)
                out_cols[spec.name] = Column(res, cnt > 0, SqlType.double())
            continue
        if spec.fn in ("min", "max"):
            ident = _agg_identity(spec.fn, ac.data.dtype)
            d = jnp.where(weight, ac.data, ident)
            res = _lowcard_reduce(spec.fn, d, gid, nseg)
            out_cols[spec.name] = Column(
                res, cnt > 0, _agg_result_type(spec.fn, ac.dtype),
                sdict=ac.sdict)
            continue
        return None  # unsupported agg: caller falls back to sort path

    diag.note("groupby", "masked")
    return Relation(columns=out_cols, mask=occupied)


def _count_distinct(minor_to_major, key_cols, rel, spec, n):
    """COUNT(DISTINCT arg): re-sort by (group keys, arg) and count
    first-occurrence flags per group."""
    ac = eval_expr(spec.arg, rel)
    mm = [ac.data] + list(minor_to_major)
    order2 = _lexsort(mm)
    # recompute lanes in the second order
    m = rel.mask_or_true()
    l2 = jnp.take(m, order2)
    d2 = jnp.take(ac.data, order2)
    v2 = jnp.take(ac.valid, order2) if ac.valid is not None else None
    w2 = l2 if v2 is None else (l2 & v2)
    # group ids in second order: recompute boundaries on group keys
    # (validity lanes participate — a NULL-key group must not merge with
    # the canonicalized-payload group, mirroring the first sort)
    diff = jnp.zeros(n, dtype=jnp.bool_)
    for c in key_cols.values():
        kd = jnp.take(c.data, order2)
        diff = diff | jnp.concatenate([jnp.ones(1, jnp.bool_), kd[1:] != kd[:-1]])
        if c.valid is not None:
            kv = jnp.take(c.valid, order2)
            diff = diff | jnp.concatenate(
                [jnp.ones(1, jnp.bool_), kv[1:] != kv[:-1]]
            )
    if not key_cols:
        diff = jnp.concatenate([jnp.ones(1, jnp.bool_), jnp.zeros(n - 1, jnp.bool_)])
    newgrp2 = diff & l2
    gid2 = jnp.where(l2, jnp.maximum(jnp.cumsum(newgrp2.astype(jnp.int64)) - 1, 0),
                     n - 1)
    newval = jnp.concatenate([jnp.ones(1, jnp.bool_), d2[1:] != d2[:-1]])
    first = (newgrp2 | newval) & w2
    return jax.ops.segment_sum(first.astype(jnp.int64), gid2, num_segments=n)


def scalar_agg(rel: Relation, aggs: Sequence[AggSpec]) -> Relation:
    """Aggregates without GROUP BY -> single-row relation (always 1 live row,
    SQL semantics: COUNT over empty input is 0, SUM/MIN/MAX are NULL)."""
    m = rel.mask_or_true()
    out: dict[str, Column] = {}
    for spec in aggs:
        if spec.fn == "count_star":
            v = jnp.sum(m.astype(jnp.int64))
            out[spec.name] = Column(v[None], None, SqlType.int_())
            continue
        assert spec.arg is not None
        ac = eval_expr(spec.arg, rel)
        if ac.dtype.kind == TypeKind.BOOL:
            ac = cast_column(ac, SqlType.int_())
        weight = m if ac.valid is None else (m & ac.valid)
        cnt = jnp.sum(weight.astype(jnp.int64))
        if spec.fn == "count":
            out[spec.name] = Column(cnt[None], None, SqlType.int_())
            continue
        if spec.fn == "count_distinct":
            order = _lexsort((ac.data,))
            d = jnp.take(ac.data, order)
            w = jnp.take(weight, order)
            newval = jnp.concatenate([jnp.ones(1, jnp.bool_), d[1:] != d[:-1]])
            v = jnp.sum((newval & w).astype(jnp.int64))
            out[spec.name] = Column(v[None], None, SqlType.int_())
            continue
        if spec.fn in ("sum", "avg"):
            d = jnp.where(weight, ac.data, jnp.zeros((), ac.data.dtype))
            s = jnp.sum(d)
            if spec.fn == "sum":
                out[spec.name] = Column(s[None], (cnt > 0)[None],
                                        _agg_result_type("sum", ac.dtype))
            else:
                if ac.dtype.kind == TypeKind.DECIMAL:
                    num = s.astype(jnp.float64) / (10 ** ac.dtype.scale)
                else:
                    num = s.astype(jnp.float64)
                res = num / jnp.maximum(cnt, 1).astype(jnp.float64)
                out[spec.name] = Column(res[None], (cnt > 0)[None], SqlType.double())
            continue
        if spec.fn in ("min", "max"):
            ident = _agg_identity(spec.fn, ac.data.dtype)
            d = jnp.where(weight, ac.data, ident)
            v = jnp.min(d) if spec.fn == "min" else jnp.max(d)
            out[spec.name] = Column(v[None], (cnt > 0)[None], ac.dtype,
                                    sdict=ac.sdict)
            continue
        raise ValueError(spec.fn)
    return Relation(columns=out, mask=None)


# ---------------------------------------------------------------------------
# join
# ---------------------------------------------------------------------------

def _combined_key(cols: Sequence[Column]):
    """Combine join key columns into one sortable int64.

    Single int-like key -> raw value (exact, no verification needed).
    Multi-key / string-pairs -> 64-bit mix; caller must verify candidates.
    """
    exact = len(cols) == 1 and cols[0].dtype.kind in (
        TypeKind.INT, TypeKind.DATE, TypeKind.DATETIME, TypeKind.DECIMAL,
        TypeKind.BOOL, TypeKind.STRING,
    )
    return keyhash.combine([c.data for c in cols], jnp,
                           raw_single=exact), exact


def _keys_valid(cols: Sequence[Column], mask):
    v = mask
    for c in cols:
        if c.valid is not None:
            v = v & c.valid
    return v


# A probe merges when ``ln * ceil(log2(rn))`` (the gathers one binary
# search makes) exceeds this, and searches below it.  On a TPU v5e (PR 25,
# int64 keys, rn = 262,144) the merge RUNS faster at every size tried:
# 1.9 ms against the one search's 5.7 at 16,384 lanes, 2.3 / 39.7 at
# 131,072, 6.7 / 484 at 1,048,576, 64.9 / 4,107 at 8,388,608 (the two
# searches it replaces: 7,586).  What it costs is the compiler: its two
# sorts take 24-47 s a probe, cold, where the search takes 5-9 s.  At this
# constant (233,000 lanes into 262,144 keys) the search is 0.07-0.11 s of
# an execution (17-27 ns a gather): under it a program keeps its compile
# time, over it the probe would be most of the statement.
_MERGE_PROBE_MIN_GATHERS = 1 << 22


def _ranks_by_merge(rn: int, ln: int, sorts: int = 2) -> bool:
    """The shape rule: do ``ln`` probe keys rank against ``rn`` build keys
    by merging (or by binary searches)?  ``sorts``: how many sorts the
    merge costs the compiler over the search; the constant above is the
    price of two."""
    work = ln * max(rn - 1, 1).bit_length()
    return work * 2 > _MERGE_PROBE_MIN_GATHERS * sorts \
        and rn + ln < 2 ** 31


def _probe_ranges(build_sorted: jax.Array, probe_keys: jax.Array,
                  _path: str | None = None):
    """For every probe key the range ``[lo, hi)`` of equal keys in the
    sorted build side: exactly ``jnp.searchsorted(build_sorted,
    probe_keys, side="left")`` and ``side="right"``, lane for lane.

    Two ways to compute the one answer, chosen from the static shapes
    (``_path`` is for the tests that hold both to the contract):

    - ``merge``: sort build and probe keys together by (key, position),
      build rows first, so a build row precedes every probe row of equal
      key; ``hi`` is then the running count of build rows and ``lo`` that
      count at the start of the run of equal keys; one more sort by
      position brings both back to probe order (21.5 ms at 8.4M lanes
      where a permutation scatter takes 107; PR 25).  Sequential access
      only.
    - ``search``: one binary search for ``lo`` (``log2(rn)`` dependent
      gathers a lane, cheap to compile), and ``hi`` read off the end of
      the build side's run of equal keys where the key is there.
    """
    rn, ln = build_sorted.shape[0], probe_keys.shape[0]
    if _path is None:
        _path = "merge" if _ranks_by_merge(rn, ln) else "search"
    diag.note("probe", _path)
    if _path == "search":
        lo = jnp.searchsorted(build_sorted, probe_keys, side="left")
        idx = lax.iota(lo.dtype, rn)
        last = jnp.concatenate([build_sorted[1:] != build_sorted[:-1],
                                jnp.ones(1, jnp.bool_)])
        # a forward scan over the flipped rows: ``reverse=True`` costs the
        # TPU compiler 49 s at 262,144 rows, this form 6 (compiled for a
        # described v5e in the sandbox, PR 25)
        run_end = jnp.flip(lax.cummin(jnp.flip(
            jnp.where(last, idx + 1, rn))))
        at = jnp.minimum(lo, rn - 1)
        found = (lo < rn) & (jnp.take(build_sorted, at) == probe_keys)
        return lo, jnp.where(found, jnp.take(run_end, at), lo)
    keys, pos = _sort_with_rows(
        (jnp.concatenate([build_sorted, probe_keys]),))
    is_build = (pos < rn).astype(jnp.int32)
    hi_all = jnp.cumsum(is_build)
    first = jnp.concatenate([jnp.ones(1, jnp.bool_), keys[1:] != keys[:-1]])
    # the count before a run of equal keys never decreases along the
    # sorted rows, so a running maximum carries it through the run
    lo_all = lax.cummax(jnp.where(first, hi_all - is_build, 0))
    _, lo, hi = lax.sort((pos, lo_all, hi_all), num_keys=1, is_stable=False)
    return lo[rn:], hi[rn:]


def join(
    left: Relation,
    right: Relation,
    left_keys: Sequence[ir.Expr],
    right_keys: Sequence[ir.Expr],
    how: str = "inner",
    out_capacity: int | None = None,
    build_unique: bool = False,
    noted_as: str | None = None,
) -> Relation:
    """Sort-based equi-join; probe side = left, build side = right.

    how: inner | left | semi | anti.
    Column names must be disjoint (the planner qualifies them).
    NULL join keys never match (SQL equi-join semantics).
    ``build_unique``: the planner found the build side unique on the key
    (``HashJoin.build_unique``); an inner or left join on an exact key
    then emits on the probe's lanes (``_join_on_probe_lanes``).
    ``noted_as``: the kind ``plan.join_kinds`` counts this join under
    where it is not ``how`` (a semi-join with a residual expands as an
    inner join).
    """
    diag.note("join_kind", noted_as or how)
    ln, rn = left.capacity, right.capacity
    lm, rm = left.mask_or_true(), right.mask_or_true()

    if not left_keys:  # cross join: constant key matches everything
        left_keys = [ir.Literal(0)]
        right_keys = [ir.Literal(0)]
    lcols = [eval_expr(e, left) for e in left_keys]
    rcols = [eval_expr(e, right) for e in right_keys]
    # string keys across different dictionaries: translate left into right's
    for i, (lc, rc) in enumerate(zip(lcols, rcols)):
        if lc.dtype.is_string and rc.dtype.is_string and lc.sdict is not rc.sdict:
            lcols[i] = _translate_dict(lc, rc)
        if lc.dtype.kind == TypeKind.DECIMAL or rc.dtype.kind == TypeKind.DECIMAL:
            s = max(lc.dtype.scale, rc.dtype.scale)
            lcols[i] = cast_column(lc, SqlType(TypeKind.DECIMAL, 38, s))
            rcols[i] = cast_column(rc, SqlType(TypeKind.DECIMAL, 38, s))

    lkey, exact = _combined_key(lcols)
    rkey, rexact = _combined_key(rcols)
    exact = exact and rexact
    lvalid = _keys_valid(lcols, lm)
    rvalid = _keys_valid(rcols, rm)

    # build: sort right by key, dead/null-key rows pushed to the end
    BIG = jnp.asarray(_INT_MAX, dtype=jnp.int64)
    rkey_s = jnp.where(rvalid, rkey, BIG)
    if build_unique and exact and how in ("inner", "left"):
        return _join_on_probe_lanes(
            left, right, jnp.where(lvalid, lkey, BIG - 1), lvalid, rkey_s,
            how)
    if exact and how in ("semi", "anti"):
        # membership alone: is there a build row of the probe's key?  What
        # a join on its probe's lanes asks, whatever repeats on the build
        # side; NULL keys never match, so NOT EXISTS keeps them (NOT IN's
        # null-poisoning is the planner's, layered on top)
        matched = lvalid & (_match_rows(
            rkey_s, jnp.where(lvalid, lkey, BIG - 1))[0] < rn)
        return left.with_mask(lm & (matched if how == "semi" else ~matched))
    # the two expensive steps carry a scope of their own: HLO metadata
    # only (device ops read "HashJoin#k/join.probe" in a profile), no
    # cache key sees it
    with jax.named_scope("join.sort_build"):
        border = _lexsort((rkey_s,))
        rkey_sorted = jnp.take(rkey_s, border)
    n_build = jnp.sum(rvalid.astype(jnp.int64))

    lkey_p = jnp.where(lvalid, lkey, BIG - 1)
    with jax.named_scope("join.probe"):
        lo, hi = _probe_ranges(rkey_sorted, lkey_p)
    # lo/hi ∈ [0, rn] so counts <= rn always — no clamp needed
    counts = jnp.where(lvalid, hi - lo, 0)

    # inexact (hash-combined) semi/anti expand too: candidate counts
    # include hash collisions, so matches must be verified

    keep_unmatched = how in ("left", "full")
    if keep_unmatched:
        ecounts = jnp.where(lm, jnp.maximum(counts, 1), 0)
    else:
        ecounts = counts
    cap = out_capacity if out_capacity is not None else max(ln, rn)

    total = jnp.sum(ecounts)
    diag.note("join_emit", "expanded")
    # static-capacity overflow is a hard error surfaced by the executor
    # (≙ DTL backpressure made compile-time; see exec/diag.py)
    diag.push("join_overflow", jnp.maximum(total - cap, 0),
              capacity=cap)
    start = jnp.cumsum(ecounts) - ecounts  # exclusive prefix
    probe_idx = jnp.repeat(jnp.arange(ln), ecounts, total_repeat_length=cap)
    out_live = jnp.arange(cap) < total
    off = jnp.arange(cap) - jnp.take(start, probe_idx)
    matched = jnp.take(counts, probe_idx) > 0
    bpos = jnp.clip(jnp.take(lo, probe_idx) + off, 0, rn - 1)
    build_idx = jnp.take(border, bpos)

    out_cols: dict[str, Column] = {}
    for name, c in left.columns.items():
        out_cols[name] = c.gather(probe_idx)
    bvalid_lane = out_live & matched
    null_extend = how in ("left", "full")
    for name, c in right.columns.items():
        g = c.gather(build_idx)
        v = g.valid_or_true() & bvalid_lane if null_extend else g.valid
        out_cols[name] = Column(g.data, v if null_extend else g.valid,
                                c.dtype, c.sdict)

    live = out_live & (matched | (jnp.asarray(keep_unmatched)))
    match_lane = out_live & matched  # lanes carrying a real build pairing
    if not exact:
        # verify candidate equality on the real key columns (hash collisions)
        ok = jnp.ones(cap, dtype=jnp.bool_)
        for lc, rc in zip(lcols, rcols):
            lg = jnp.take(lc.data, probe_idx)
            rg = jnp.take(rc.data, build_idx)
            ok = ok & (lg == rg)
        true_lane = out_live & matched & ok
        # true-match re-count per probe row: collisions must neither emit
        # phantom NULL-extended rows nor satisfy semi/anti membership
        tc = jax.ops.segment_sum(true_lane.astype(jnp.int64), probe_idx,
                                 num_segments=ln)
        if how == "semi":
            return left.with_mask(lm & (tc > 0))
        if how == "anti":
            return left.with_mask(lm & (tc == 0))
        if how in ("left", "full"):
            # a lane survives as a real match, or as the single
            # NULL-extended row when its probe row has no true match
            tc_g = jnp.take(tc, probe_idx)
            null_lane = (off == 0) & (tc_g == 0)
            live = out_live & (true_lane | null_lane)
            match_lane = true_lane
            for name in right.columns:
                c = out_cols[name]
                out_cols[name] = Column(c.data,
                                        c.valid_or_true() & true_lane,
                                        c.dtype, c.sdict)
        else:
            live = live & ok

    if how == "full":
        # FULL OUTER: append one lane per build row, live when that row
        # matched no probe lane (NULL-extended left side) — unmatched-
        # build emission, ≙ ObHashJoinVecOp's FILL_RIGHT phase
        # (src/sql/engine/join/hash_join/ob_hash_join_vec_op.h:342)
        bmatch = jax.ops.segment_sum(
            match_lane.astype(jnp.int64),
            jnp.where(match_lane, build_idx, rn),  # rn = dropped
            num_segments=max(rn, 1))
        app_live = rm & (bmatch == 0)
        zeros = jnp.zeros(rn, dtype=jnp.int64)
        full_cols: dict[str, Column] = {}
        for name, c in out_cols.items():
            if name in left.columns:
                app = left.columns[name].gather(zeros)
                app = Column(app.data, jnp.zeros(rn, jnp.bool_),
                             app.dtype, app.sdict)
            else:
                rc = right.columns[name]
                app = Column(rc.data, rc.valid, rc.dtype, rc.sdict)
            full_cols[name] = Column(
                jnp.concatenate([c.data, app.data]),
                jnp.concatenate([c.valid_or_true(),
                                 app.valid_or_true()]),
                c.dtype, c.sdict)
        return Relation(columns=full_cols,
                        mask=jnp.concatenate([live, app_live]))

    return Relation(columns=out_cols, mask=live)


def _running_max(x: jax.Array) -> jax.Array:
    """``lax.cummax`` of non-negative int64 lanes, in two levels (within
    rows of 1,024 lanes, then over the rows' maxima): one flat 64-bit scan
    costs the TPU compiler 183 s at 393,216 lanes and 146 s at 524,288
    (11 s at 4,194,304), this form 5 s at each (compiled for a described
    v5e in the sandbox, PR 39; ``vector/column.py::_live_through`` found
    the same of a flat ``cumsum``)."""
    n = x.shape[0]
    rows = jnp.pad(x, (0, -n % SCAN_ROW)).reshape(-1, SCAN_ROW)
    within = lax.cummax(rows, axis=1)
    over_rows = lax.cummax(within[:, -1])
    before = jnp.concatenate([jnp.zeros(1, x.dtype), over_rows[:-1]])
    return jnp.maximum(within, before[:, None]).reshape(-1)[:n]


def _unique_match_by_merge(rkey_s: jax.Array, lkey_p: jax.Array):
    """For every probe key the row of the ONE build key equal to it
    (``>= rn``: none), and how many build keys repeat another: build and
    probe keys sorted together by (key, position), build rows first, so a
    run of equal keys starts with its build row if it has one; that head's
    row is carried through the run by a running maximum over (sorted
    position, row) packed into one int64, and one more sort by position
    brings it back to probe order.  Two sorts and a scan, sequential
    access only: no sort of the build side alone, no ``lo`` / ``hi``, and
    no gather through a permutation (a random gather costs the TPU 20 ns
    an element, a sort 4 ns a lane; PERF.md section 5)."""
    rn = rkey_s.shape[0]
    keys, pos = _sort_with_rows((jnp.concatenate([rkey_s, lkey_p]),))
    same = keys[1:] == keys[:-1]
    # a build row behind an equal key follows a build row: a repeat
    dups = jnp.sum((same & (pos[1:] < rn)
                    & (keys[1:] != _INT_MAX)).astype(jnp.int64))
    first = jnp.concatenate([jnp.ones(1, jnp.bool_), ~same])
    at = lax.iota(jnp.int64, keys.shape[0])
    head = _running_max(jnp.where(first,
                                  (at << 32) | pos.astype(jnp.int64), 0))
    head_row = (head & 0xFFFFFFFF).astype(jnp.int32)
    _, row = lax.sort((pos, head_row), num_keys=1, is_stable=False)
    return row[rn:], dups


def _unique_match_by_search(rkey_s: jax.Array, lkey_p: jax.Array):
    """``_unique_match_by_merge``'s answer where the probe is too small to
    pay for the merge's compile (``_ranks_by_merge``): the build side
    sorted alone, its sorted keys taken FROM the sort, one
    binary search a probe key and one gather through the permutation."""
    rn = rkey_s.shape[0]
    with jax.named_scope("join.sort_build"):
        rkey_sorted, border = _sort_with_rows((rkey_s,))
    dups = jnp.sum(((rkey_sorted[1:] == rkey_sorted[:-1])
                    & (rkey_sorted[1:] != _INT_MAX)).astype(jnp.int64))
    at = jnp.minimum(jnp.searchsorted(rkey_sorted, lkey_p, side="left"),
                     rn - 1)
    found = jnp.take(rkey_sorted, at) == lkey_p
    return jnp.where(found, jnp.take(border, at), rn), dups


def _match_rows(rkey_s: jax.Array, lkey_p: jax.Array):
    """For every probe key a build row of that key (``>= rn``: none), and
    how many build keys repeat another; by merge or by search from the
    static shapes.  The merge sorts twice and the search once (the build
    side): one sort more to compile, not ``_probe_ranges``' two, so it
    pays from half the gathers (Q14 at SF1, 131,072 lanes into 262,144
    keys: 2.5 ms merged, about 35 searched; my chip run, PR 39)."""
    path = "merge" if _ranks_by_merge(
        rkey_s.shape[0], lkey_p.shape[0], sorts=1) else "search"
    diag.note("probe", path)
    with jax.named_scope("join.probe"):
        return (_unique_match_by_merge if path == "merge"
                else _unique_match_by_search)(rkey_s, lkey_p)


def _join_on_probe_lanes(left: Relation, right: Relation,
                         lkey_p: jax.Array, lvalid: jax.Array,
                         rkey_s: jax.Array, how: str) -> Relation:
    """Inner / left join against a build side that holds each key once:
    every probe row pairs with at most one build row, so the output stays
    on the probe's lanes.  The probe's columns pass as they are, the
    build's are gathered once by the matched row; no prefix sum, no
    ``jnp.repeat``, and nothing can overflow.

    ``rkey_s``: the build keys, ``_INT_MAX`` on dead / NULL-key lanes;
    ``lkey_p``: the probe keys, ``_INT_MAX - 1`` on such lanes.

    The guarantee is the planner's reading of a declared primary key; it
    is checked here: build keys that repeat another are counted on the
    ``join_build_dup`` lane, and a count above zero makes the session
    re-plan with the mark off."""
    rn = right.capacity
    row, dups = _match_rows(rkey_s, lkey_p)
    diag.note("join_emit", "probe_lanes")
    diag.push("join_build_dup", dups)
    matched = lvalid & (row < rn)
    build_idx = jnp.minimum(row, rn - 1)
    out_cols = dict(left.columns)
    for name, c in right.columns.items():
        g = c.gather(build_idx)
        if how == "left":
            g = Column(g.data, g.valid_or_true() & matched, c.dtype, c.sdict)
        out_cols[name] = g
    lm = left.mask_or_true()
    return Relation(columns=out_cols,
                    mask=lm & matched if how == "inner" else lm)


def index_probe(
    probe: Relation,
    sidecar: Relation,
    base: Relation,
    key: ir.Expr,
    columns: Sequence[str] | None,
    rename: dict[str, str] | None,
    out_capacity: int | None = None,
) -> Relation:
    """Index nested-loop join: ``_probe_ranges`` of ``key`` into a
    PRE-SORTED index sidecar, then a positional gather of the base
    table's rows — the build-side argsort a hash join pays every
    execution is amortized into the (cached, host-built) sidecar.

    sidecar: ``__key__`` sorted int64 over the base's LIVE rows with
    valid keys, padded with _INT_MAX; ``__pos__`` the matching row
    positions into ``base``'s raw arrays.  Keys are exact ints (the
    planner only picks this path for single int-like columns), so every
    expanded lane is a true match — no verification pass.
    NULL/dead probe keys never match (equi-join semantics).
    """
    ln = probe.capacity
    lm = probe.mask_or_true()
    kc = eval_expr(key, probe)
    lkey = kc.data.astype(jnp.int64)
    lvalid = _keys_valid([kc], lm)

    skey = sidecar.columns["__key__"].data
    spos = sidecar.columns["__pos__"].data
    sn = sidecar.capacity

    BIG = jnp.asarray(_INT_MAX, dtype=jnp.int64)
    # BIG-1 (not BIG): the pad keys are BIG, so a dead probe lane's
    # sentinel must sort strictly below them to report zero matches
    lkey_p = jnp.where(lvalid, lkey, BIG - 1)
    with jax.named_scope("join.probe"):
        lo, hi = _probe_ranges(skey, lkey_p)
    counts = jnp.where(lvalid, hi - lo, 0)

    cap = out_capacity if out_capacity is not None else max(ln, sn)
    total = jnp.sum(counts)
    diag.push("index_probe_overflow", jnp.maximum(total - cap, 0),
              capacity=cap)
    start = jnp.cumsum(counts) - counts  # exclusive prefix
    probe_idx = jnp.repeat(jnp.arange(ln), counts,
                           total_repeat_length=cap)
    out_live = jnp.arange(cap) < total
    off = jnp.arange(cap) - jnp.take(start, probe_idx)
    span = jnp.clip(jnp.take(lo, probe_idx) + off, 0, sn - 1)
    base_idx = jnp.take(spos, span)

    out_cols: dict[str, Column] = {}
    for name, c in probe.columns.items():
        out_cols[name] = c.gather(probe_idx)
    names = columns if columns is not None else list(base.columns)
    for bname in names:
        g = base.columns[bname].gather(base_idx)
        out_cols[(rename or {}).get(bname, bname)] = g
    # every live lane is a real match: the sidecar holds only live rows
    # with valid keys and int equality needs no verification
    return Relation(columns=out_cols, mask=out_live)


def semi_join_residual(
    left: Relation,
    right: Relation,
    left_keys: Sequence[ir.Expr],
    right_keys: Sequence[ir.Expr],
    residual: Sequence[ir.Expr],
    anti: bool = False,
    out_capacity: int | None = None,
) -> Relation:
    """Semi/anti join with non-equality correlated predicates.

    ≙ the reference's semi-join with other_join_conds (hash join NON-EQUI
    conditions in ObHashJoinVecOp).  Strategy: expand the equality join,
    evaluate the residual on the combined rows, then reduce matches per
    probe row (segment_sum over the probe index) — EXISTS keeps rows with
    >0 surviving matches, NOT EXISTS keeps rows with 0.
    """
    ln = left.capacity
    lm = left.mask_or_true()
    # tag probe rows with their position so matches fold back per-row
    rid = Column(jnp.arange(ln, dtype=jnp.int64), None, SqlType.int_())
    left2 = Relation(columns={**left.columns, "__rid__": rid}, mask=left.mask)
    expanded = join(left2, right, left_keys, right_keys, how="inner",
                    out_capacity=out_capacity,
                    noted_as="anti" if anti else "semi")
    ok = expanded.mask_or_true()
    for pred in residual:
        from oceanbase_tpu.expr.compile import eval_predicate

        ok = ok & eval_predicate(pred, expanded)
    ridx = jnp.clip(expanded.columns["__rid__"].data, 0, ln - 1)
    matches = jax.ops.segment_sum(ok.astype(jnp.int64), ridx,
                                  num_segments=ln)
    if anti:
        return left.with_mask(lm & (matches == 0))
    return left.with_mask(lm & (matches > 0))


def concat(rels: Sequence[Relation]) -> Relation:
    """UNION ALL: stack relations (same column ids) into one.

    String columns with different dictionaries are re-encoded into a merged
    dictionary (host work at trace time, device gather to remap).
    """
    names = list(rels[0].columns)
    out_cols: dict[str, Column] = {}
    for name in names:
        cols = [r.columns[name] for r in rels]
        if any(c.sdict is not None for c in cols):
            dicts = [c.sdict for c in cols if c.sdict is not None]
            if all(d is dicts[0] for d in dicts):
                merged = dicts[0]
            else:
                allvals = np.unique(np.concatenate([d.values for d in dicts]))
                merged = StringDict(allvals)
                new_cols = []
                for c in cols:
                    remap = np.searchsorted(
                        merged.values, c.sdict.values).astype(np.int32)
                    codes = jnp.asarray(remap)[
                        jnp.clip(c.data, 0, c.sdict.size - 1)]
                    new_cols.append(Column(codes, c.valid, c.dtype, merged))
                cols = new_cols
            data = jnp.concatenate([c.data for c in cols])
            out_cols[name] = Column(data, _concat_valid(cols),
                                    cols[0].dtype, merged)
            continue
        data = jnp.concatenate([c.data.astype(cols[0].data.dtype)
                                for c in cols])
        out_cols[name] = Column(data, _concat_valid(cols), cols[0].dtype)
    mask = jnp.concatenate([r.mask_or_true() for r in rels])
    return Relation(columns=out_cols, mask=mask)


def _concat_valid(cols):
    if all(c.valid is None for c in cols):
        return None
    return jnp.concatenate([c.valid_or_true() for c in cols])


def _translate_dict(lc: Column, rc: Column) -> Column:
    """Map left dict codes into right's dictionary space (-1 = no match)."""
    assert lc.sdict is not None and rc.sdict is not None
    pos = np.searchsorted(rc.sdict.values, lc.sdict.values)
    posc = np.clip(pos, 0, max(rc.sdict.size - 1, 0))
    exact = rc.sdict.values[posc] == lc.sdict.values if rc.sdict.size else \
        np.zeros(lc.sdict.size, dtype=bool)
    lut = np.where(exact, posc, -1).astype(np.int32)
    codes = jnp.asarray(lut)[jnp.clip(lc.data, 0, lc.sdict.size - 1)]
    valid = lc.valid
    # codes == -1 never match any live right code because right codes >= 0,
    # except right code -1 payloads of NULLs — those are masked by validity.
    return Column(codes, valid, SqlType.string(), rc.sdict)
