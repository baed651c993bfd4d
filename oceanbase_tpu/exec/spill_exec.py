"""Spill-orchestrated SQL execution: whole plans over inputs that exceed
the device/work-area budget.

Reference analog: the SQL memory manager deciding per-operator spill
(src/sql/engine/ob_tenant_sql_memory_manager.h) driving the spillable
operators — external merge sort (src/sql/engine/sort/ob_sort_vec_op.h),
recursive hash-partition join (ob_hash_join_vec_op.h:413), and the
dump-capable group-by (ob_hash_groupby_vec_op.cpp) — all backed by the
temp-file system (src/storage/tmp_file).

The TPU shape of the same idea, in two halves.

ON THE DEVICE (``exec/granule.py``): a plan that is a union over the
granules of ONE over-budget table (a scan pipeline, probes of
device-resident build sides, under a group-by or a scalar aggregate)
streams that table granule by granule through one cached chunk program:
scan, filter, project, probe and the partial aggregate run there, the
partial states stay on the device and one more cached program merges
them, finishes the aggregate and applies the coordinator chain.  Nothing
of such a statement crosses to the host but its result.

ON THE HOST AND THE DISK (here): what does not fit the device that way.
A streamed ORDER BY drains each granule's surviving rows to the host and
sorts them in runs (exec/external_sort.py); a group-by whose partial
states outgrow the work area drains them and merges sorted runs by key;
a join with both sides over the budget co-partitions both streams
(exec/spill.py::partitioned_join_spilled) — all in the temp-file store
(storage/tmpfile.py), capped by ``temporary_file_max_disk_size``.
Per-batch operators of this half run the same `exec.ops` kernels
eagerly.

Plan shapes (dispatch in :func:`execute_spilled`):

- ``[Project*/Limit?/Sort?] over [GroupBy|ScalarAgg] over a union over
  one table's granules``                       -> all on the device
- the same with a group-by state over the budget -> partials to the host,
  disk merge by key (unbounded NDV)
- ``[Project*/Limit?/Sort?] over such a union`` -> streamed sort
- a join tree with two over-budget sides        -> each HashJoin either
  probes a device-resident build side batch by batch or co-partitions to
  disk.  LEFT joins stream only on the preserved side.

Anything else raises NotDistributable; the session counts it
(``spill.fallbacks{reason}``) and runs the resident plan.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field

import numpy as np

from oceanbase_tpu.exec import diag, ops
from oceanbase_tpu.exec import plan as pp
from oceanbase_tpu.exec.external_sort import external_sort
from oceanbase_tpu.exec.granule import (
    DEFAULT_CHUNK_ROWS,
    GranulePlan,
    _find_single_scan,
    granule_rows_for,
    merge_outputs,
    stream_outputs,
)
from oceanbase_tpu.exec.spill import partitioned_join_spilled
from oceanbase_tpu.expr import ir
from oceanbase_tpu.px.dist_ops import split_aggs
from oceanbase_tpu.px.planner import NotDistributable, split_top
from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.server import trace as qtrace
from oceanbase_tpu.storage.tmpfile import TempFileStore
from oceanbase_tpu.vector import Relation, from_numpy, to_numpy

# spill-tier accounting (host side, recorded once per spilled query at
# the result boundary — same place the spill.execute span closes)
qmetrics.declare("spill.executions", "counter",
                 "queries routed through the disk-spill tier")
qmetrics.declare("spill.bytes", "counter",
                 "bytes written to the temp-file store")
qmetrics.declare("spill.rows", "counter",
                 "rows that crossed the host/disk boundary")
qmetrics.declare("spill.execute_s", "histogram",
                 "spilled-query wall time", unit="s")
qmetrics.declare("spill.fallbacks", "counter",
                 "statements priced over the work area that could not "
                 "stream and ran the resident plan, by reason")

OUT_CHUNK = 1 << 16

_STREAM = "__stream__"  # placeholder scan name for per-batch lowering


@dataclass
class SpillStats:
    """What the query spilled (surfaced in EXPLAIN ANALYZE + v$sql_workarea,
    ≙ the work-area profile the reference exposes per operator)."""

    kind: str = ""            # sort | groupby | join | scalar | mixed
    runs: int = 0             # temp-file runs created
    bytes: int = 0            # bytes written to the temp-file store
    spilled_rows: int = 0     # rows that crossed the host/disk boundary
    batches: int = 0          # streamed batches processed
    ops: list = field(default_factory=list)  # [(op kind, detail)]


class _Ctx:
    def __init__(self, store: TempFileStore, budget_rows: int,
                 chunk_rows: int, providers: dict, device_tables: dict,
                 types_by_table: dict, big_tables: set,
                 budget_bytes: int | None = None):
        self.store = store
        self.budget_rows = budget_rows
        self.budget_bytes = budget_bytes
        self.chunk_rows = chunk_rows
        self.providers = providers
        self.device_tables = device_tables
        self.types_by_table = types_by_table
        self.big_tables = big_tables
        self.stats = SpillStats()
        self.dtypes: dict[str, object] = {}  # col name -> SqlType

    def note(self, op: str, detail: str = ""):
        self.stats.ops.append((op, detail))

    def snap_store(self):
        self.stats.runs = self.store._next
        self.stats.bytes = self.store.bytes_written

    def record_dtypes(self, rel: Relation):
        for name, col in rel.columns.items():
            self.dtypes[name] = col.dtype


@dataclass
class Spilled:
    """A streamed statement's result: ``relation`` where it was finished
    on the device, else the host columns the disk half produced."""

    stats: SpillStats
    relation: Relation | None = None
    arrays: dict = field(default_factory=dict)
    valids: dict = field(default_factory=dict)
    dtypes: dict = field(default_factory=dict)

    def host(self):
        """-> (arrays, valids, dtypes, stats), a device result fetched."""
        if self.relation is not None:
            raw = to_numpy(self.relation)
            cols = [c for c in raw if not c.startswith("__valid__")]
            self.arrays = {c: raw[c] for c in cols}
            self.valids = {c: raw["__valid__" + c] for c in cols
                           if "__valid__" + c in raw}
            self.dtypes = {c: self.relation.columns[c].dtype for c in cols}
            self.relation = None
        return self.arrays, self.valids, self.dtypes, self.stats


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def execute_spilled(plan: pp.PlanNode, providers: dict, spill_dir: str,
                    budget_rows: int, device_tables: dict | None = None,
                    types_by_table: dict | None = None,
                    big_tables: set | None = None,
                    chunk_rows: int = DEFAULT_CHUNK_ROWS,
                    disk_budget=None, faults=None, label: str = "",
                    budget_bytes: int | None = None) -> Spilled:
    """Run ``plan`` with the over-budget tables streamed, and disk spill
    for everything over ``budget_rows``.

    providers: {table: chunk_provider} for the over-budget tables
    (re-iterable granule streams).  device_tables: {table: Relation} for
    every other referenced table (lowered whole).  The granule is the
    largest ladder rung, ``chunk_rows`` at most, of which the buffers in
    flight fit ``budget_rows`` rows (``budget_bytes``, where the work area
    is one in bytes, is asserted against the granule's real widths).
    -> Spilled; raises NotDistributable for unsupported shapes.

    ``disk_budget``/``faults``/``label`` thread the disk-pressure plane
    into the temp-file store: chunk writes are accounted against the
    tenant's ``temporary_file_max_disk_size`` (SpillBudgetExceeded kills
    just this statement) and consult the fault plane (seeded ENOSPC/EIO,
    kind="spill").
    """
    chunk_rows = granule_rows_for(budget_rows, chunk_rows)
    big = set(big_tables if big_tables is not None else providers)
    if not big:
        raise NotDistributable("no over-budget table to stream")
    missing = big - set(providers)
    if missing:
        raise NotDistributable(f"no chunk provider for {sorted(missing)}")

    import time as _time

    m0 = _time.monotonic()
    with TempFileStore(spill_dir, budget=disk_budget, faults=faults,
                       label=label) as store, \
            qtrace.span("spill.execute") as tsp:
        ctx = _Ctx(store, budget_rows, chunk_rows, providers,
                   device_tables or {}, types_by_table or {}, big,
                   budget_bytes)
        gp = None
        if len(big) == 1:
            try:
                gp = GranulePlan(plan, next(iter(big)), chunk_rows)
            except NotDistributable:
                gp = None       # the host half may still take the shape
        try:
            out = _stream_one_table(ctx, gp) if gp is not None \
                else _stream_tree(ctx, plan)
        finally:
            ctx.snap_store()
        if any(k == "join" for k, _ in ctx.stats.ops):
            ctx.stats.kind = ("join" if ctx.stats.kind == "sort"
                              else ctx.stats.kind + "+join")
        tsp.tags.update(kind=ctx.stats.kind, runs=ctx.stats.runs,
                        bytes=ctx.stats.bytes,
                        spilled_rows=ctx.stats.spilled_rows,
                        batches=ctx.stats.batches)
        qmetrics.inc("spill.executions", kind=ctx.stats.kind)
        qmetrics.inc("spill.bytes", ctx.stats.bytes, kind=ctx.stats.kind)
        qmetrics.inc("spill.rows", ctx.stats.spilled_rows,
                     kind=ctx.stats.kind)
        qmetrics.observe("spill.execute_s", _time.monotonic() - m0,
                         kind=ctx.stats.kind)
        return out


def _has_join(node: pp.PlanNode) -> bool:
    return isinstance(node, (pp.HashJoin, pp.SemiJoinResidual,
                             pp.IndexProbe)) \
        or any(_has_join(c) for c in node.children())


def _granule_outputs(ctx: _Ctx, gp: GranulePlan):
    """The device outputs of ``gp``'s chunk program, a granule each."""
    for out in stream_outputs(gp, ctx.providers[gp.table],
                              ctx.device_tables,
                              ctx.types_by_table.get(gp.table),
                              ctx.budget_bytes):
        ctx.stats.batches += 1
        yield out


def _stream_one_table(ctx: _Ctx, gp: GranulePlan) -> Spilled:
    """``gp.table`` streams through one chunk program.  An aggregate's
    partial states stay on the device while they fit the work area beside
    the granules, and merge there; states that outgrow it, and the rows of
    a statement that only sorts, drain to the host half."""
    ctx.note("scan-stream", gp.table)
    if _has_join(gp.inner):
        ctx.note("join", "probe-resident")
    ctx.stats.kind = ("groupby" if gp.group is not None else
                      "scalar" if gp.scalar is not None else "sort")
    stream = _granule_outputs(ctx, gp)
    if gp.aggregates:
        held, lanes, drained = [], 0, False
        for out in stream:
            held.append(out)
            lanes += out.capacity
            # the merge program pads its inputs to a power of two
            if gp.group is not None and 2 * lanes > ctx.budget_rows:
                drained = True
                break
        if not drained:
            return Spilled(ctx.stats, relation=merge_outputs(gp, held))
        ctx.note("groupby", "state over the work area: disk merge")

        def partial_batches():
            for out in held:
                yield from _host_batch(ctx, out, counted=False)
            held.clear()
            for out in stream:
                yield from _host_batch(ctx, out, counted=False)

        batches = _merge_group_partials(
            ctx, partial_batches(), list(gp.group.keys), gp.final_specs,
            gp.post)
    else:
        def batches_of():
            for out in stream:
                yield from _host_batch(ctx, out, counted=False)

        batches = batches_of()
    with qtrace.span("spill.finish"):
        arrays, valids = _finish(ctx, batches, gp.top)
    return Spilled(ctx.stats, None, arrays, valids, dict(ctx.dtypes))


def _stream_tree(ctx: _Ctx, plan: pp.PlanNode) -> Spilled:
    """The host half's general walk: every over-budget scan streams to
    host batches, joins between two of them co-partition to disk, the
    aggregate folds batch by batch."""
    top, scalar_agg, droot = split_top(plan)
    group_node = None
    if isinstance(droot, pp.GroupBy):
        group_node = droot
        inner = droot.child
    else:
        inner = droot

    def _split(aggs):
        # the spill tier's public contract is NotDistributable for every
        # unsupported shape — including non-splittable aggregates
        try:
            return split_aggs(aggs)
        except NotImplementedError as e:
            raise NotDistributable(str(e)) from None

    batches = _stream_subtree(ctx, inner)
    if group_node is not None:
        partial_specs, final_specs, post = _split(group_node.aggs)
        keys = group_node.keys
        batches = _partial_groupby_batches(ctx, batches, keys,
                                           partial_specs)
        batches = _merge_group_partials(ctx, batches, list(keys),
                                        final_specs, post)
        ctx.stats.kind = "groupby"
    elif scalar_agg is not None:
        partial_specs, final_specs, post = _split(scalar_agg.aggs)
        batches = _partial_scalar_batches(ctx, batches, partial_specs)
        batches = _scalar_final(ctx, batches, final_specs, post)
        ctx.stats.kind = "scalar"
    else:
        ctx.stats.kind = "sort"
    # the granule streams above are lazy: _finish drives them, so the
    # whole spill pipeline's work lands inside the caller's span
    # (closing at the host result boundary)
    with qtrace.span("spill.finish"):
        arrays, valids = _finish(ctx, batches, top)
    return Spilled(ctx.stats, None, arrays, valids, dict(ctx.dtypes))


# ---------------------------------------------------------------------------
# streaming the input tree
# ---------------------------------------------------------------------------


def _is_scan_pipeline(node) -> bool:
    if isinstance(node, pp.TableScan):
        return True
    if isinstance(node, (pp.Filter, pp.Project, pp.Compact)):
        return _is_scan_pipeline(node.child)
    return False


def _stream_subtree(ctx: _Ctx, node: pp.PlanNode):
    """-> host (arrays, valids) batch iterator for a subtree that
    references at least one over-budget table."""
    refs = set(pp.referenced_tables(node))
    if not (refs & ctx.big_tables):
        raise NotDistributable("subtree has no streamed table")
    if _is_scan_pipeline(node):
        table = _find_single_scan(node)
        if table not in ctx.providers:
            raise NotDistributable(f"no chunk provider for {table}")
        return _scan_batches(ctx, node, table)
    if isinstance(node, (pp.Filter, pp.Project, pp.Compact)):
        child_batches = _stream_subtree(ctx, node.child)
        wrapper = dataclasses.replace(node, child=pp.TableScan(_STREAM))
        return _batch_apply(ctx, wrapper, child_batches)
    if isinstance(node, pp.HashJoin):
        return _stream_join(ctx, node)
    raise NotDistributable(
        f"cannot stream {type(node).__name__} over budget")


def _scan_batches(ctx: _Ctx, subtree: pp.PlanNode, table: str):
    """Granules -> the cached chunk program of a scan pipeline -> host
    batches of the rows that survive it."""
    gp = GranulePlan(subtree, table, ctx.chunk_rows, subtree=True)

    def gen():
        for out in _granule_outputs(ctx, gp):
            yield from _host_batch(ctx, out, counted=False)

    ctx.note("scan-stream", table)
    return gen()


def _host_batch(ctx: _Ctx, rel: Relation, counted: bool = True):
    """Device relation -> one host (arrays, valids) batch (live rows).

    Every produced batch funnels through here, which makes it the
    spill tier's per-chunk cancel/deadline checkpoint: KILL and
    query_timeout_s observe between chunk programs, host-side.
    ``counted``: a granule's output is counted where it was made."""
    from oceanbase_tpu.server import admission as qadmission

    qadmission.checkpoint()
    ctx.record_dtypes(rel)
    host = to_numpy(rel)
    cols = [c for c in host if not c.startswith("__valid__")]
    if not cols:
        return
    arrays = {c: host[c] for c in cols}
    if len(next(iter(arrays.values()))) == 0:
        return
    valids = {c: host.get("__valid__" + c) for c in cols}
    ctx.stats.batches += counted
    yield arrays, valids


def _pad_to_relation(ctx: _Ctx, arrays: dict, valids: dict):
    """Host batch -> device relation padded to a power-of-two capacity
    with a live-row mask (bounds the jit/program cache)."""
    import jax.numpy as jnp

    from oceanbase_tpu.exec.granule import _pad

    n = len(next(iter(arrays.values())))
    cap = 1
    while cap < max(n, 1):
        cap <<= 1
    pad = cap - n
    a = {k: _pad(np.asarray(v), pad) for k, v in arrays.items()}
    v = {k: _pad(np.asarray(x), pad, False)
         for k, x in (valids or {}).items() if x is not None}
    rel = from_numpy(a, types={k: t for k, t in ctx.dtypes.items()
                               if k in a}, valids=v)
    m = np.zeros(cap, dtype=bool)
    m[:n] = True
    return Relation(columns=rel.columns, mask=jnp.asarray(m))


def _batch_apply(ctx: _Ctx, wrapper: pp.PlanNode, batches):
    """Apply a plan fragment (with one TableScan(_STREAM) leaf) per host
    batch, eagerly on device."""

    def gen():
        for arrays, valids in batches:
            rel = _pad_to_relation(ctx, arrays, valids)
            out = ops.compact(pp._lower_inner(
                wrapper, {**ctx.device_tables, _STREAM: rel}))
            ctx.record_dtypes(out)
            yield from _host_batch(ctx, out)

    return gen()


# ---------------------------------------------------------------------------
# joins
# ---------------------------------------------------------------------------


def _stream_join(ctx: _Ctx, node: pp.HashJoin):
    lrefs = set(pp.referenced_tables(node.left))
    rrefs = set(pp.referenced_tables(node.right))
    lbig = bool(lrefs & ctx.big_tables)
    rbig = bool(rrefs & ctx.big_tables)
    if lbig and rbig:
        return _copartition_join(ctx, node)
    # one-side stream: build the small side whole on device, probe with
    # streamed batches.  Outer-join safety: the streamed side must be the
    # preserved side — unmatched BUILD rows cannot be emitted per batch.
    if node.how == "left" and not lbig:
        raise NotDistributable("left join with over-budget build side")
    if node.how not in ("inner", "left"):
        raise NotDistributable(f"streamed {node.how} join")
    stream_side, build_side = ((node.left, node.right) if lbig
                               else (node.right, node.left))
    skeys, bkeys = ((node.left_keys, node.right_keys) if lbig
                    else (node.right_keys, node.left_keys))
    build_rel = ops.compact(
        pp._lower_inner(build_side, ctx.device_tables))
    batches = _stream_subtree(ctx, stream_side)
    ctx.note("join", f"stream-{'left' if lbig else 'right'} "
                     f"how={node.how}")

    def gen():
        for arrays, valids in batches:
            srel = _pad_to_relation(ctx, arrays, valids)
            n = len(next(iter(arrays.values())))
            # per-batch output budget scales with the batch, not the
            # planner's whole-query estimate; the x4 retry loop recovers
            # from underestimates, and the LAST attempt falls back to the
            # planner's whole-query estimate so extreme per-key fanout
            # (>128x batch rows) still completes instead of erroring
            cap = max(2 * n, 1024)
            last = max(cap * 4 ** 4, node.out_capacity or 0)
            for _attempt in range(5):
                if _attempt == 4:
                    cap = last
                with diag.collect() as entries:
                    if lbig:
                        j = ops.join(srel, build_rel, skeys, bkeys,
                                     how=node.how, out_capacity=cap)
                    else:
                        j = ops.join(build_rel, srel, bkeys, skeys,
                                     how=node.how, out_capacity=cap)
                    dropped = sum(int(v) for _nm, v, _cap in entries)
                if dropped == 0:
                    break
                cap *= 4
            else:
                raise diag.CapacityOverflow(
                    f"streamed join batch overflows at {cap}")
            ctx.record_dtypes(j)
            yield from _host_batch(ctx, j)

    return gen()


def _copartition_join(ctx: _Ctx, node: pp.HashJoin):
    """Both sides over budget: hash co-partition both streams to disk,
    join pair-by-pair (exec/spill.py)."""
    if node.how not in ("inner", "left"):
        raise NotDistributable(f"spilled {node.how} join")

    def names(keys):
        out = []
        for k in keys:
            if not isinstance(k, ir.ColumnRef):
                raise NotDistributable("spilled join needs column keys")
            out.append(k.name)
        return out

    lnames, rnames = names(node.left_keys), names(node.right_keys)
    lbatches = _stream_subtree(ctx, node.left)
    rbatches = _stream_subtree(ctx, node.right)
    ctx.note("join", "copartition-disk")

    def counted(batches):
        for arrays, valids in batches:
            ctx.stats.spilled_rows += len(next(iter(arrays.values())))
            yield arrays, valids

    def gen():
        for arrays, valids in partitioned_join_spilled(
                counted(lbatches), counted(rbatches), lnames, rnames,
                ctx.store, how=node.how,
                budget_rows=ctx.budget_rows):
            ctx.stats.batches += 1
            # dtype capture: join output columns are the union of the
            # two sides' (already recorded) columns — nothing new
            yield arrays, valids

    return gen()


# ---------------------------------------------------------------------------
# aggregation over streams
# ---------------------------------------------------------------------------


def _partial_groupby_batches(ctx: _Ctx, batches, keys: dict,
                             partial_specs):
    def gen():
        for arrays, valids in batches:
            rel = _pad_to_relation(ctx, arrays, valids)
            out = ops.hash_groupby(rel, keys, partial_specs,
                                   out_capacity=rel.capacity)
            ctx.record_dtypes(out)
            yield from _host_batch(ctx, out)

    return gen()


def _partial_scalar_batches(ctx: _Ctx, batches, partial_specs):
    def gen():
        got = False
        rel = None
        for arrays, valids in batches:
            rel = _pad_to_relation(ctx, arrays, valids)
            out = ops.scalar_agg(rel, partial_specs)
            ctx.record_dtypes(out)
            got = True
            yield from _host_batch(ctx, out)
        if not got:
            raise NotDistributable(
                "no input batches for spilled scalar aggregate")

    return gen()


def _scalar_final(ctx: _Ctx, batches, final_specs, post):
    """Fold 1-row partial batches into the final scalar aggregates, then
    apply the post projection (avg ratios) on device."""

    def gen():
        parts_a, parts_v = [], []
        for arrays, valids in batches:
            parts_a.append(arrays)
            parts_v.append(valids)
        if not parts_a:
            return
        arrays, valids = _concat_batches(parts_a, parts_v)
        starts = np.array([0])
        out_a, out_v = _reduce_groups(arrays, valids, [], final_specs,
                                      starts, ctx.dtypes)
        yield from _post_project(ctx, out_a, out_v, {}, post)

    return gen()


def _merge_group_partials(ctx: _Ctx, batches, key_names, final_specs,
                          post):
    """External-sort partial batches by group key, merge equal-key runs
    (≙ the sort-based fallback of the dump-capable hash group-by), then
    post-project.  Handles NDV far beyond device capacity."""

    def counted(src):
        for arrays, valids in src:
            ctx.stats.spilled_rows += len(next(iter(arrays.values())))
            yield arrays, valids

    def gen():
        sorted_chunks = external_sort(
            counted(batches), key_names, [True] * len(key_names),
            ctx.store, budget_rows=ctx.budget_rows,
            out_chunk=OUT_CHUNK)
        carry = None
        for arrays, valids in sorted_chunks:
            if carry is not None:
                arrays, valids = _concat_batches(
                    [carry[0], arrays], [carry[1], valids])
            n = len(next(iter(arrays.values())))
            starts = _group_starts(arrays, valids, key_names)
            if len(starts) > 1:
                cut = starts[-1]
                head_a = {k: v[:cut] for k, v in arrays.items()}
                head_v = {k: (v[:cut] if v is not None else None)
                          for k, v in valids.items()}
                out_a, out_v = _reduce_groups(
                    head_a, head_v, key_names, final_specs, starts[:-1],
                    ctx.dtypes)
                yield from _post_project(ctx, out_a, out_v,
                                         key_names, post)
            cut = starts[-1] if len(starts) else 0
            carry = ({k: v[cut:] for k, v in arrays.items()},
                     {k: (v[cut:] if v is not None else None)
                      for k, v in valids.items()})
        if carry is not None and \
                len(next(iter(carry[0].values()))) > 0:
            arrays, valids = carry
            starts = _group_starts(arrays, valids, key_names)
            out_a, out_v = _reduce_groups(arrays, valids, key_names,
                                          final_specs, starts, ctx.dtypes)
            yield from _post_project(ctx, out_a, out_v, key_names, post)

    return gen()


def _post_project(ctx: _Ctx, arrays, valids, key_names, post):
    """Final outputs = group keys + post-projection of final aggregates;
    runs on device to get expression semantics (decimal avg etc.)."""
    outs = {k: ir.col(k) for k in key_names}
    outs.update(post)
    if all(isinstance(e, ir.ColumnRef) and e.name in arrays
           for e in outs.values()):
        out_a = {nm: arrays[e.name] for nm, e in outs.items()}
        out_v = {nm: valids.get(e.name) for nm, e in outs.items()}
        for nm, e in outs.items():
            if e.name in ctx.dtypes:
                ctx.dtypes[nm] = ctx.dtypes[e.name]
        yield out_a, out_v
        return
    rel = _pad_to_relation(ctx, arrays, valids)
    out = ops.project(rel, outs)
    ctx.record_dtypes(out)
    yield from _host_batch(ctx, out)


def _group_starts(arrays, valids, key_names) -> np.ndarray:
    """Start index of each equal-key run in key-sorted host arrays.
    NULL == NULL for grouping; NaN == NaN (sorted adjacent)."""
    n = len(next(iter(arrays.values())))
    change = np.zeros(n, dtype=bool)
    if n == 0:
        return np.zeros(0, dtype=np.int64)
    change[0] = True
    for k in key_names:
        a = arrays[k]
        if a.dtype == object:
            a = a.astype("U")
        v = valids.get(k)
        with np.errstate(invalid="ignore"):
            neq = a[1:] != a[:-1]
        if a.dtype.kind == "f":
            both_nan = np.isnan(a[1:]) & np.isnan(a[:-1])
            neq &= ~both_nan
        if v is not None:
            neq = (v[1:] != v[:-1]) | (v[1:] & v[:-1] & neq)
        change[1:] |= neq
    return np.nonzero(change)[0]


_INT_SENT = {"min": np.iinfo(np.int64).max, "max": np.iinfo(np.int64).min}


def _reduce_groups(arrays, valids, key_names, final_specs, starts,
                   dtypes: dict | None = None):
    """Merge partial-aggregate rows per equal-key group (vectorized
    ufunc.reduceat; object/NULL-heavy min/max falls back to a per-group
    loop)."""
    out_a = {k: arrays[k][starts] for k in key_names}
    out_v = {k: (valids[k][starts] if valids.get(k) is not None else None)
             for k in key_names}
    for spec in final_specs:
        pname = spec.arg.name
        if dtypes is not None and pname in dtypes:
            # a sum / min / max of a partial keeps the partial's type (a
            # DECIMAL's scale rides it to the post projection)
            dtypes[spec.name] = dtypes[pname]
        a = arrays[pname]
        v = valids.get(pname)
        if spec.fn == "sum":
            av = np.where(v, a, 0) if v is not None else a
            red = np.add.reduceat(av, starts)
            rv = (np.logical_or.reduceat(v, starts)
                  if v is not None else None)
        elif spec.fn in ("min", "max"):
            ufunc = np.minimum if spec.fn == "min" else np.maximum
            if a.dtype == object or a.dtype.kind in "US":
                red, rv = _loop_minmax(a, v, starts, spec.fn == "min")
            else:
                if v is not None:
                    if a.dtype.kind == "f":
                        sent = np.inf if spec.fn == "min" else -np.inf
                    else:
                        sent = _INT_SENT[spec.fn]
                    a = np.where(v, a, np.asarray(sent, dtype=a.dtype))
                red = ufunc.reduceat(a, starts)
                rv = (np.logical_or.reduceat(v, starts)
                      if v is not None else None)
        else:
            raise NotDistributable(f"spilled final merge of {spec.fn}")
        out_a[spec.name] = red
        out_v[spec.name] = rv
    return out_a, {k: v for k, v in out_v.items() if v is not None}


def _loop_minmax(a, v, starts, is_min):
    ends = np.append(starts[1:], len(a))
    red = np.empty(len(starts), dtype=object)
    rv = np.zeros(len(starts), dtype=bool)
    for g, (s, e) in enumerate(zip(starts, ends)):
        vals = [a[i] for i in range(s, e)
                if v is None or v[i]]
        if vals:
            red[g] = min(vals) if is_min else max(vals)
            rv[g] = True
        else:
            red[g] = ""
    return red, rv


# ---------------------------------------------------------------------------
# coordinator tail: [Project* Limit? Sort?] over a batch stream
# ---------------------------------------------------------------------------


def _finish(ctx: _Ctx, batches, top):
    """Apply the coordinator chain.  A Sort externals-sorts the stream
    (early-exit under Limit); Projects above the Sort apply to the final
    (small) result, Projects below it apply per batch."""
    sort_node = None
    limit_node = None
    above_projects = []
    below = []
    for node in top:  # outermost-first
        if sort_node is None:
            if isinstance(node, pp.Sort):
                sort_node = node
            elif isinstance(node, pp.Limit):
                if limit_node is not None:
                    raise NotDistributable("stacked limits")
                limit_node = node
            elif isinstance(node, pp.Project):
                above_projects.append(node)
        else:
            if isinstance(node, pp.Project):
                below.append(node)
            else:
                raise NotDistributable(
                    f"{type(node).__name__} under streamed Sort")
    for node in reversed(below):  # innermost-first
        wrapper = dataclasses.replace(node, child=pp.TableScan(_STREAM))
        batches = _batch_apply(ctx, wrapper, batches)

    want = None
    if limit_node is not None:
        want = limit_node.k + limit_node.offset

    if sort_node is not None:
        key_cols = []
        for k in sort_node.keys:
            if not isinstance(k, ir.ColumnRef):
                raise NotDistributable("streamed sort needs column keys")
            key_cols.append(k.name)

        def counted(src):
            for arrays, valids in src:
                ctx.stats.spilled_rows += \
                    len(next(iter(arrays.values())))
                yield arrays, valids

        stream = external_sort(counted(batches), key_cols,
                               sort_node.ascending, ctx.store,
                               budget_rows=ctx.budget_rows,
                               out_chunk=OUT_CHUNK)
    else:
        stream = batches

    parts_a, parts_v = [], []
    got = 0
    for arrays, valids in stream:
        parts_a.append(arrays)
        parts_v.append(valids)
        got += len(next(iter(arrays.values())))
        if want is not None and got >= want:
            break  # merge tail stays on disk
    if not parts_a:
        return {}, {}
    arrays, valids = _concat_batches(parts_a, parts_v)
    if limit_node is not None:
        lo, hi = limit_node.offset, limit_node.offset + limit_node.k
        arrays = {c: a[lo:hi] for c, a in arrays.items()}
        valids = {c: (v[lo:hi] if v is not None else None)
                  for c, v in valids.items()}
    for node in reversed(above_projects):  # innermost-first
        outs = node.outputs
        if all(isinstance(e, ir.ColumnRef) for e in outs.values()):
            for nm, e in outs.items():
                if e.name in ctx.dtypes:
                    ctx.dtypes[nm] = ctx.dtypes[e.name]
            arrays = {nm: arrays[e.name] for nm, e in outs.items()}
            valids = {nm: valids.get(e.name) for nm, e in outs.items()}
        else:
            rel = _pad_to_relation(ctx, arrays, valids)
            out = ops.project(rel, outs)
            ctx.record_dtypes(out)
            host = to_numpy(out)
            cols = [c for c in host if not c.startswith("__valid__")]
            arrays = {c: host[c] for c in cols}
            valids = {c: host.get("__valid__" + c) for c in cols}
    return arrays, {k: v for k, v in valids.items() if v is not None}


def _concat_batches(parts_a, parts_v):
    cols = list(parts_a[0])
    arrays = {}
    valids = {}
    for c in cols:
        chunks = [np.asarray(p[c]) for p in parts_a]
        if any(x.dtype == object for x in chunks):
            chunks = [x.astype(object) for x in chunks]
        arrays[c] = np.concatenate(chunks)
        if any(v.get(c) is not None for v in parts_v):
            valids[c] = np.concatenate(
                [np.asarray(v[c]) if v.get(c) is not None
                 else np.ones(len(a[c]), dtype=bool)
                 for v, a in zip(parts_v, parts_a)])
        else:
            valids[c] = None
    return arrays, valids
