"""Granule streaming: execute scan pipelines over tables larger than the
work area.

Reference analog: the granule iterator + pump (ObGranuleIteratorOp,
ObGranulePump::fetch_granule_task, src/sql/engine/px/ob_granule_pump.cpp:361)
— a scan proceeds granule-by-granule with operator rescan.  On TPU the
granule is a fixed-shape host->HBM chunk, and one statement is:

- host, producer thread (``granule.fetch``): the provider decodes ONE
  granule's 65,536-row segment chunks, only the columns the plan reaches,
  dictionary-coded strings as int32 codes of the table's dictionary, zone
  maps pruning chunks before any decode, MVCC's newest-wins and tombstones
  held with array operations (``segment_chunk_provider``);
- host -> device (``granule.upload``): the granule's columns padded to
  the granule shape, always with a row mask, so every granule of a
  statement has one input signature;
- device (``granule.program``): ONE program per (chunk plan as it is
  lowered, granule shape) from the executable cache every plan uses
  (``exec/plan.py::executable_for``, rows in ``gv$plan_cache``): scan,
  filter, project, the probes of device-resident build sides and the
  partial aggregate, each BUDGETED FOR ONE GRANULE (``granule_budget``,
  the one place that says what that is).  Partial states stay on the
  device;
- device (``granule.merge``): the partial states, the final aggregate, the
  post projection and the coordinator chain (sort / limit / project) as
  one more cached program.

Uploads overlap the previous granule's program (``prefetch_iter``); the
granule's bytes times the buffers in flight stay under the work area
(``stream_outputs`` asserts it).  A granule that drops a row for its
budget ends the stream there (``diag.CapacityOverflow``): the session
re-plans by what was dropped and keeps the factor that cleared.  What
does not fit the device this way
(sorted runs, group-by states over the budget, joins with both sides over
it) is ``exec/spill_exec.py``'s, on the host and in the temp-file store.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np

from oceanbase_tpu.datatypes import TypeKind
from oceanbase_tpu.exec import diag
from oceanbase_tpu.exec import plan as pp
from oceanbase_tpu.expr import ir
from oceanbase_tpu.px.dist_ops import split_aggs
from oceanbase_tpu.px.planner import NotDistributable, split_top
from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.server import trace as qtrace
from oceanbase_tpu.storage.encoding import CodedStrings, decode_column_into
from oceanbase_tpu.vector import Relation, bucket_capacity
from oceanbase_tpu.vector.column import Column, StringDict

DEFAULT_CHUNK_ROWS = 1 << 21  # ~2M rows per granule
#: granules the producer thread may hold ready ahead of the consumer
PREFETCH_DEPTH = 2
#: granule buffers alive at once: the queue's, the one the producer is
#: building and the one the chunk program reads
BUFFERS_IN_FLIGHT = PREFETCH_DEPTH + 2

_PARTIAL = "__partial_{}__"   # the merge program's input tables

qmetrics.declare("granule.count", "counter",
                 "granules streamed through a chunk program")
qmetrics.declare("granule.rows", "counter",
                 "live rows of the streamed granules")
qmetrics.declare("granule.upload_bytes", "counter",
                 "bytes of granule columns copied host -> device")
qmetrics.declare("granule.pruned_chunks", "counter",
                 "segment chunks zone maps skipped before decode")
qmetrics.declare("granule.budget_lanes", "counter",
                 "per chunk-program execution, the capacities its nodes "
                 "over the streamed table were lowered with (Compact, "
                 "join outputs: granule_budget's share of the plan's)")
qmetrics.declare("granule.plan_budget_lanes", "counter",
                 "per chunk-program execution, the capacities the "
                 "statement's plan gave the same nodes (over "
                 "granule.budget_lanes: what a granule's budget saves)")


def snap_chunk_rows(chunk_rows: int) -> int:
    """Snap a granule capacity onto the shared bucket ladder: chunk
    programs compile per chunk shape, so an arbitrary (config-derived)
    chunk size must not mint a fresh executable per value."""
    return bucket_capacity(chunk_rows)


def granule_rows_for(budget_rows: int,
                     chunk_rows: int = DEFAULT_CHUNK_ROWS) -> int:
    """The granule's lanes under a work area that holds ``budget_rows``
    rows of the streamed table: the largest ladder rung, ``chunk_rows`` at
    most, of which ``BUFFERS_IN_FLIGHT`` fit."""
    fit = max(int(budget_rows) // BUFFERS_IN_FLIGHT, 1)
    cap = snap_chunk_rows(min(chunk_rows, fit))
    while cap > fit and cap > 64:
        cap //= 2
    return cap


def _find_single_scan(node):
    """The streamed subtree must read exactly one base table."""
    tabs = pp.referenced_tables(node)
    if len(tabs) != 1:
        raise NotDistributable("streaming needs a single-table subtree")
    return next(iter(tabs))


# ---------------------------------------------------------------------------
# zone-map bounds
# ---------------------------------------------------------------------------

_POW10 = [10 ** k for k in range(40)]
_FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "=": "="}
_INTEGRAL = (TypeKind.INT, TypeKind.BOOL)


def _stored_bound(v: int, lit_t, col_t):
    """``v``, a literal of type ``lit_t``, in the stored representation of
    a column of type ``col_t`` (a DECIMAL is stored as an integer scaled by
    its column's scale, a DATE as days), exactly; ``None`` where the two
    do not compare by one integer (the conjunct then prunes nothing)."""
    lk, ck = lit_t.kind, col_t.kind
    if ck in _INTEGRAL or ck == TypeKind.DECIMAL:
        if lk in _INTEGRAL:
            lscale = 0
        elif lk == TypeKind.DECIMAL:
            lscale = lit_t.scale
        else:
            return None
        cscale = col_t.scale if ck == TypeKind.DECIMAL else 0
        if lscale <= cscale:
            return v * _POW10[cscale - lscale]
        q, r = divmod(v, _POW10[lscale - cscale])
        return q if r == 0 else None
    if ck in (TypeKind.DATE, TypeKind.DATETIME):
        return v if lk == ck else None
    return None


def extract_column_bounds(node, types: dict | None = None,
                          table: str | None = None) -> dict:
    """Collect per-source-column [lo, hi] bounds from the Filter chain for
    zone-map chunk pruning (≙ the white filters the blockscan applies on
    index-block aggregates before decoding micro blocks).

    Only top-level AND conjuncts of the shape col cmp literal survive;
    everything else is simply not used for pruning (safe over-approx).  A
    bound is taken in the stored representation of the COLUMN (``types``:
    source column -> SqlType): ``l_quantity < 24`` on a DECIMAL(15,2)
    column is ``hi = 2400``.  Without ``types`` only a literal whose own
    representation is an integer (int, date, datetime, bool) gives one, as
    if the column were of the literal's type.
    ``table``: the scans of this table alone give columns (a join's other
    side may name a column alike).
    Returns {source_col: (lo|None, hi|None)} in SOURCE column names
    (TableScan rename reversed)."""
    from oceanbase_tpu.expr.compile import literal_value

    bounds: dict[str, list] = {}
    scan_cols: dict[str, str] = {}    # column id -> source column
    redefined: set = set()            # ids a Project computes anew

    def visit(nd):
        """Post-order: a filter's conjuncts are read when the scans and
        projections under it are known."""
        for c in nd.children():
            visit(c)
        if isinstance(nd, pp.TableScan) and table in (None, nd.table):
            if nd.rename:
                for src, cid in nd.rename.items():
                    scan_cols[cid] = src
            elif types:
                scan_cols.update((c, c) for c in (nd.columns or types))
        elif isinstance(nd, pp.Project):
            redefined.update(
                nm for nm, e in nd.outputs.items()
                if not (isinstance(e, ir.ColumnRef) and e.name == nm))
        elif isinstance(nd, pp.Filter):
            for conj in conjuncts(nd.pred):
                one(conj)

    def conjuncts(e):
        if isinstance(e, ir.Logic) and e.op == "and":
            for a in e.args:
                yield from conjuncts(a)
        else:
            yield e

    def one(e):
        if not isinstance(e, ir.Cmp):
            return
        col, lit_, op = None, None, e.op
        if isinstance(e.left, ir.ColumnRef) and isinstance(e.right, ir.Literal):
            col, lit_ = e.left.name, e.right
        elif isinstance(e.right, ir.ColumnRef) and \
                isinstance(e.left, ir.Literal):
            col, lit_ = e.right.name, e.left
            op = _FLIP.get(op)
        if col is None or op not in _FLIP or col in redefined:
            return
        try:
            v, t = literal_value(lit_)
        except Exception:  # noqa: BLE001 — non-foldable literal
            return
        if isinstance(v, (bool, np.bool_)):
            v = int(v)
        if not isinstance(v, (int, np.integer)):
            return
        src = scan_cols.get(col, col if types is None else None)
        if src is None:
            return
        if types is None:
            if t.kind.value not in ("int", "date", "datetime", "bool"):
                return
            v = int(v)
        else:
            if src not in types:
                return
            v = _stored_bound(int(v), t, types[src])
            if v is None:
                return
        lo, hi = bounds.get(src, [None, None])
        if op in (">", ">=", "="):
            lo = v if lo is None else max(lo, v)
        if op in ("<", "<=", "="):
            hi = v if hi is None else min(hi, v)
        bounds[src] = [lo, hi]

    visit(node)
    return {k: tuple(v) for k, v in bounds.items()}


# ---------------------------------------------------------------------------
# the producer thread
# ---------------------------------------------------------------------------


def prefetch_iter(it, depth: int = PREFETCH_DEPTH, trace=None):
    """Overlap host-side granule production (LSM decode, upload, disk
    reads) with device compute: a daemon thread runs the producer ahead
    into a small bounded queue (≙ the IO manager's async prefetch,
    src/share/io/ob_io_manager.h — here one prefetcher per stream).

    ``trace``: (TraceCtx, parent span id) of the statement, so that the
    spans the producer opens hang in the statement's tree.
    Exceptions in the producer re-raise at the consumer's next pull.
    Abandoning the iterator (early break / GeneratorExit — a LIMIT that
    stops mid-stream) stops the producer and CLOSES the wrapped
    generator from its own thread, so provider finalizers (open LSM /
    spill file handles) still run."""
    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=depth)
    _END = object()
    stop = threading.Event()

    def put_until_stopped(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def run():
        try:
            with qtrace.activate(*(trace or (None,))):
                for item in it:
                    if not put_until_stopped(item):
                        break
        except BaseException as e:  # noqa: BLE001 — ship to consumer
            put_until_stopped(("__exc__", e))
            return
        finally:
            if stop.is_set() and hasattr(it, "close"):
                # generator close must run on the thread that executes
                # the generator — that's this one
                try:
                    it.close()
                except Exception:
                    pass
        put_until_stopped(_END)

    t = threading.Thread(target=run, daemon=True,
                         name="granule-prefetch")
    t.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                return
            if isinstance(item, tuple) and len(item) == 2 and \
                    item[0] == "__exc__":
                raise item[1]
            yield item
    finally:
        stop.set()
        t.join(timeout=5)


# ---------------------------------------------------------------------------
# host granule -> device relation
# ---------------------------------------------------------------------------


def _pad(v, pad, fill=0):
    if pad <= 0 or v is None:
        return v
    if v.dtype == object or v.dtype.kind in "US":
        return np.concatenate([v, np.array([""] * pad, dtype=object)])
    return np.concatenate(
        [v, np.full((pad,) + v.shape[1:], fill, dtype=v.dtype)])


def granule_layout(names, types: dict, valid_cols=()) -> int:
    """Bytes of one lane of a granule holding ``names``: each column's
    element, a validity byte where it has one, the row mask."""
    return 1 + sum(
        (4 if types[c].is_string else types[c].np_dtype.itemsize)
        + (c in valid_cols) for c in names)


def _chunk_to_relation(arrays, valids, types, dicts, chunk_rows, n):
    """One host granule -> a device relation of exactly ``chunk_rows``
    lanes, ALWAYS with a row mask (a full granule and the last one share
    one program).  A string column arrives as ``CodedStrings`` over the
    provider's dictionary; the ``StringDict`` rides as static metadata.
    -> (relation, bytes copied)."""
    from oceanbase_tpu.datatypes import SqlType
    from oceanbase_tpu.vector.column import encode_host

    pad = chunk_rows - n
    nbytes = 0

    def put(a, fill=0):
        nonlocal nbytes
        a = _pad(a, pad, fill)
        nbytes += a.nbytes
        return jax.device_put(a)

    plain = {k: v for k, v in arrays.items()
             if not isinstance(v, CodedStrings)}
    for k, v in plain.items():
        if v.dtype == object or v.dtype.kind in "US":
            raise NotDistributable(
                f"granule column {k!r} holds strings without a dictionary")
    host = encode_host(plain, {k: t for k, t in (types or {}).items()
                               if k in plain}, valids)
    cols: dict[str, Column] = {}
    for k, v in arrays.items():
        if k in host:
            hc = host[k]
            data, valid, t, sd = hc.data, hc.valid, hc.dtype, None
        else:
            data = v.codes.astype(np.int32, copy=False)
            valid = (valids or {}).get(k)
            t, sd = (types or {}).get(k) or SqlType.string(), dicts[k]
        cols[k] = Column(put(data),
                         None if valid is None
                         else put(valid.astype(np.bool_, copy=False), False),
                         t, sd)
    return Relation(columns=cols,
                    mask=put(np.ones(n, dtype=np.bool_), False)), nbytes


def _uploaded(granules, types, dicts, chunk_rows, counts):
    """Host granules -> device relations, in the producer thread: one
    ``granule.fetch`` span around each pull of the provider (its decode),
    one ``granule.upload`` around the copy, waited for so that the bytes
    over the span are the copy's rate and a buffer is whole before it
    counts as ready."""
    it = iter(granules)
    while True:
        with qtrace.span("granule.fetch") as fsp:
            item = next(it, None)
            if item is None:
                fsp.tags.update(rows=0, chunks_pruned=counts.take_pruned())
                break
            arrays, valids = item
            n = len(next(iter(arrays.values()))) if arrays else 0
            fsp.tags.update(rows=n, chunks_pruned=counts.take_pruned())
        if n == 0:
            continue
        if n > chunk_rows:
            raise ValueError(f"granule of {n} rows over {chunk_rows} lanes")
        with qtrace.span("granule.upload") as usp:
            rel, nbytes = _chunk_to_relation(arrays, valids, types, dicts,
                                             chunk_rows, n)
            jax.block_until_ready(rel)  # obcheck: ok(trace.host-sync)
            usp.tags.update(rows=n, bytes=nbytes)
        counts.granules += 1
        counts.rows += n
        counts.upload_bytes += nbytes
        yield rel


class _Counts:
    """What one statement's stream did; booked once, at its end."""

    def __init__(self, provider, gp: "GranulePlan"):
        self.granules = self.rows = self.upload_bytes = self.pruned = 0
        self.programs = 0       # chunk-program executions
        self._provider, self._gp = provider, gp
        self._seen = 0

    def take_pruned(self) -> int:
        """Chunks the provider pruned since the last call."""
        total = int(getattr(self._provider, "pruned_chunks", 0))
        new, self._seen = total - self._seen, total
        self.pruned += new
        return new

    def book(self):
        qmetrics.inc("granule.count", self.granules)
        qmetrics.inc("granule.rows", self.rows)
        qmetrics.inc("granule.upload_bytes", self.upload_bytes)
        qmetrics.inc("granule.pruned_chunks", self.pruned)
        qmetrics.inc("granule.budget_lanes",
                     self.programs * self._gp.budget_lanes)
        qmetrics.inc("granule.plan_budget_lanes",
                     self.programs * self._gp.plan_budget_lanes)


# ---------------------------------------------------------------------------
# the chunk program and the merge program
# ---------------------------------------------------------------------------


def linear_in(node: pp.PlanNode, table: str) -> bool:
    """Whether ``node``'s rows over ``table`` are the union of its rows
    over the granules of ``table``: a chain of filters, projections and
    compactions, inner joins, and outer / semi / anti joins that keep the
    streamed side, with ``table`` scanned exactly once."""
    if isinstance(node, pp.TableScan):
        return True
    if isinstance(node, (pp.Filter, pp.Project, pp.Compact)):
        return linear_in(node.child, table)
    if isinstance(node, (pp.HashJoin, pp.SemiJoinResidual)):
        in_left = table in pp.referenced_tables(node.left)
        in_right = table in pp.referenced_tables(node.right)
        if in_left and in_right:
            return False
        if not (in_left or in_right):
            return True
        if isinstance(node, pp.SemiJoinResidual):
            return in_left and linear_in(node.left, table)
        if node.how == "inner":
            return linear_in(node.left if in_left else node.right, table)
        return node.how == "left" and in_left and \
            linear_in(node.left, table)
    return False


def _scans_of(node: pp.PlanNode, table: str) -> int:
    n = int(isinstance(node, (pp.TableScan, pp.IndexProbe))
            and node.table == table)
    return n + sum(_scans_of(c, table) for c in node.children())


def granule_budget(capacity: int, chunk_rows: int,
                   share: float | None = None,
                   within_granule: bool = True) -> int:
    """The lanes ONE granule's chunk program gives a node to which the
    statement's plan gave ``capacity`` (the binder's slack and whatever
    the overflow ladder multiplied in are in it).  The one place that
    says what a granule's budget is:

    - ``share``, the granule's lanes over the streamed table's rows as the
      estimate saw them: rows that DIVIDE among the granules (a
      ``Compact``'s survivors, a join's matches) get that share of the
      capacity, rounded UP to the bucket ladder.  ``None`` for what does
      not divide: a group-by's groups (each of Q1's granules holds all
      four);
    - ``within_granule``: the node puts out no more rows than its granule
      has lanes (a ``Compact``, a group-by; not a join, which may expand);
    - never above ``capacity``: a budget the ladder raised until nothing
      dropped ends at the program the whole table's capacities give."""
    budget = capacity
    if share is not None:
        budget = min(budget, bucket_capacity(math.ceil(capacity * share)))
    return min(budget, chunk_rows) if within_granule else budget


def _streamed_share(inner: pp.PlanNode, table: str,
                    chunk_rows: int) -> float | None:
    """A granule's share of ``table``: its lanes over the rows the
    estimate gave the table's one scan; ``None`` where the scan carries
    no estimate."""
    (scan,) = [n for n in pp._postorder(inner)
               if isinstance(n, pp.TableScan) and n.table == table]
    return min(chunk_rows / scan.est_rows, 1.0) if scan.est_rows else None


def _rebudgeted(node: pp.PlanNode, table: str, chunk_rows: int,
                share: float, lanes: list) -> pp.PlanNode:
    """``node`` (what ``linear_in`` accepts) with every static capacity
    over the streamed ``table`` at the granule's budget.  A subtree that
    does not scan ``table`` keeps its capacities: it sees the whole of
    its tables in every granule; a node without a capacity takes its
    input's lanes and goes on doing so.  ``lanes``: [lowered, the
    plan's], the re-budgeted nodes' capacities summed."""
    if table not in pp.referenced_tables(node):
        return node
    updates = {}
    for f in ("child", "left", "right"):
        kid = getattr(node, f, None)
        if kid is not None:
            new = _rebudgeted(kid, table, chunk_rows, share, lanes)
            if new is not kid:
                updates[f] = new
    budget = "capacity" if isinstance(node, pp.Compact) else "out_capacity"
    given = getattr(node, budget, None)
    if given is not None:
        mine = granule_budget(given, chunk_rows, share,
                              within_granule=isinstance(node, pp.Compact))
        lanes[0] += mine
        lanes[1] += given
        if mine != given:
            updates[budget] = mine
    return dataclasses.replace(node, **updates) if updates else node


class GranulePlan:
    """A plan split for granule streaming over ONE table: the program a
    granule runs (``chunk``: the plan's subtree under its aggregate, with
    the partial aggregate on top), and the program that finishes the
    statement over the granules' outputs (``merge_plan``).

    The chunk program is budgeted for ONE granule (``granule_budget``):
    the binder sized the subtree for the whole table, a granule holds its
    share.  Every ``Compact.capacity`` and join ``out_capacity`` on a node
    whose subtree scans the streamed table shrinks to the granule's share
    of the estimate (Q14 at SF10: 2,097,152 lanes of 60.0M rows, so the
    2,097,152-lane bucket of the month's filter becomes 131,072); the
    partial group-by keeps its capacity under the granule's lanes and is
    not scaled (groups do not divide among granules); resident subtrees
    and a plan whose streamed scan carries no estimate keep what they
    have.  A granule that holds more than its budget (a table clustered on
    the filter's column) overflows as any static budget does, and the
    ladder's factor, applied to the plan BEFORE the share, ends at the
    whole table's capacities."""

    def __init__(self, plan: pp.PlanNode, table: str, chunk_rows: int,
                 subtree: bool = False):
        """``subtree``: ``plan`` is a scan pipeline inside a larger plan
        (the host half's walk drains its granules' rows): no aggregate and
        no coordinator chain are split off it."""
        top, scalar_agg, droot = ([], None, plan) if subtree \
            else split_top(plan)
        group = None
        if isinstance(droot, pp.GroupBy) and scalar_agg is None \
                and not subtree:
            group, inner = droot, droot.child
        else:
            inner = droot
        if _scans_of(inner, table) != 1 or not linear_in(inner, table):
            raise NotDistributable(
                f"the plan under its aggregate is not a union over the "
                f"granules of {table}")
        #: the re-budgeted nodes' capacities as lowered and as the
        #: statement's plan has them (``granule.budget_lanes`` and
        #: ``granule.plan_budget_lanes`` count them an execution)
        self.budget_lanes = self.plan_budget_lanes = 0
        share = _streamed_share(inner, table, chunk_rows)
        if share is not None:
            lanes = [0, 0]
            inner = _rebudgeted(inner, table, chunk_rows, share, lanes)
            self.budget_lanes, self.plan_budget_lanes = lanes
        self.plan, self.table, self.chunk_rows = plan, table, chunk_rows
        self.top, self.inner = top, inner
        self.group, self.scalar = group, scalar_agg
        self.final_specs = self.post = None
        agg = group or scalar_agg
        if agg is not None:
            try:
                partial, self.final_specs, self.post = split_aggs(agg.aggs)
            except NotImplementedError as e:
                raise NotDistributable(str(e)) from None
        if group is not None:
            self.chunk = dataclasses.replace(
                group, child=inner, aggs=partial, below_join=False,
                out_capacity=granule_budget(
                    group.out_capacity or chunk_rows, chunk_rows))
        elif scalar_agg is not None:
            self.chunk = dataclasses.replace(scalar_agg, child=inner,
                                             aggs=partial)
        else:
            self.chunk = inner

    @property
    def aggregates(self) -> bool:
        return self.group is not None or self.scalar is not None

    def chunk_executable(self):
        """The chunk program, cached by the chunk plan AS LOWERED: the
        budgets are a function of the statistics (``est_rows`` is outside
        a plan's fingerprint on purpose), so two executions whose
        estimates give another bucket are two programs."""
        fingerprint = self.chunk.fingerprint()
        return pp.executable_for(pp.Program(
            pp._lower, (self.chunk,),
            ("granule", fingerprint, self.chunk_rows),
            f"granule(lanes={self.chunk_rows}) {fingerprint}"))

    def merge_plan(self, n_inputs: int) -> pp.PlanNode:
        """The statement's rest over ``n_inputs`` granule outputs: their
        union, the final aggregate, the post projection (avg as sum over
        count) and the coordinator chain."""
        inputs = [pp.TableScan(_PARTIAL.format(i)) for i in range(n_inputs)]
        node = inputs[0] if n_inputs == 1 else pp.Union(inputs)
        if self.group is not None:
            keys = list(self.group.keys)
            node = pp.GroupBy(node, {k: ir.col(k) for k in keys},
                              self.final_specs,
                              out_capacity=self.group.out_capacity)
            node = pp.Project(node, {**{k: ir.col(k) for k in keys},
                                     **self.post})
        elif self.scalar is not None:
            node = pp.ScalarAgg(node, self.final_specs)
            node = pp.Project(node, dict(self.post))
        for nd in reversed(self.top):
            node = dataclasses.replace(nd, child=node)
        return node


def merge_inputs(n: int) -> int:
    """Inputs of the merge program for ``n`` granule outputs: a power of
    two, so that a table growing by a granule keeps its program (the
    inputs beyond ``n`` are dead copies)."""
    return bucket_capacity(n, floor=1)


def stream_outputs(gp: GranulePlan, provider, device_tables: dict,
                   types: dict | None, budget_bytes: int | None = None):
    """Run ``gp.chunk`` over every granule of ``gp.table`` -> iterator of
    the granules' device outputs.  The FIRST granule that drops a row for
    a static budget ends the stream (``diag.CapacityOverflow``, as
    ``execute_plan`` raises it): a re-plan costs the granules up to that
    one, not the table.  A scan that yields no granule runs the program
    once over an all-dead one: the statement answers as the resident plan
    does over no rows."""
    from oceanbase_tpu.server import admission as qadmission

    table, chunk_rows = gp.table, gp.chunk_rows
    # the columns the program reaches, of those the provider holds
    reach = pp.scan_columns(gp.chunk)
    names = list(getattr(provider, "columns", None) or types or ()) or None
    if names and reach is not None and reach[1].get(table) is not None:
        names = [c for c in names
                 if any(r.get(c, c) in reach[0]
                        for r in reach[1][table])] or names[:1]
    scan_types = {c: types[c] for c in names if c in types} \
        if names and types else None
    bounds = extract_column_bounds(gp.inner, types, table)
    takes_names = getattr(provider, "takes_names", False)
    dicts = provider.string_dicts(names) \
        if hasattr(provider, "string_dicts") else {}
    if budget_bytes is not None and scan_types and \
            len(scan_types) == len(names):
        lane = granule_layout(names, scan_types, names)
        need = lane * chunk_rows * BUFFERS_IN_FLIGHT
        assert need <= budget_bytes, (
            f"{BUFFERS_IN_FLIGHT} granules of {chunk_rows} lanes x {lane} B "
            f"= {need} B over the work area's {budget_bytes} B")
    exe = gp.chunk_executable()
    counts = _Counts(provider, gp)
    granules = provider(table, chunk_rows, bounds, names) if takes_names \
        else provider(table, chunk_rows, bounds)
    ctx = qtrace.current()
    stream = prefetch_iter(
        _uploaded(granules, scan_types, dicts, chunk_rows, counts),
        trace=(ctx, qtrace.current_span_id()) if ctx is not None else None)

    def run(rel):
        qadmission.checkpoint()
        with qtrace.span("granule.program") as psp:
            (out, lanes, total, _mon), compiled_now, _fl, _nb, noted = \
                exe.call({**device_tables, table: rel})
            exe.stats.executions += 1
            counts.programs += 1
            if compiled_now:
                psp.tags["compiled"] = 1
            # the wait bounds the buffers in flight: this granule's
            # columns go when its program has read them
            jax.block_until_ready(total)  # obcheck: ok(trace.host-sync)
        diag.book_notes(noted)
        _check_overflow(exe, total, lanes)
        return out

    try:
        for rel in stream:
            yield run(rel)
        if not counts.granules:
            if not scan_types or len(scan_types) != len(names):
                raise NotDistributable("no granule and no column types to "
                                       "make an empty one from")
            dead = {c: (CodedStrings(np.zeros(0, np.int32), dicts[c].values)
                        if t.is_string else np.zeros(0, t.np_dtype))
                    for c, t in scan_types.items()}
            has_valid = getattr(provider, "valid_columns", lambda _n: ())(
                names)
            rel, _ = _chunk_to_relation(
                dead, {c: np.zeros(0, np.bool_) for c in has_valid},
                scan_types, dicts, chunk_rows, 0)
            yield run(rel)
    finally:
        # a stream that ends early (an overflow, a LIMIT) stops its
        # producer here, so that a re-plan's stream never runs beside it
        stream.close()
        counts.book()


def _check_overflow(exe, total, lanes):
    """One granule's overflow total, read where the consumer has waited
    for it anyway -> ``diag.CapacityOverflow`` with the lanes that dropped
    rows, the count lanes booked otherwise."""
    head = np.asarray(total).reshape(-1)  # obcheck: ok(trace.host-sync)
    if int(head[0]) == 0:
        diag.book_counts(exe.count_names, head[1:])
        return
    found = [(name, cap, v) for (name, cap), lane
             in zip(exe.diag_names, lanes)
             if (v := int(lane)) > 0]  # obcheck: ok(trace.host-sync)
    raise diag.CapacityOverflow(
        "granule program capacity exceeded ("
        + ", ".join(f"{n}={v}" for n, _c, v in found)
        + " rows dropped); re-plan with larger out_capacity",
        drops=found)


def merge_outputs(gp: GranulePlan, outputs: list) -> Relation:
    """The granules' outputs -> the statement's result, on the device:
    one cached program over a power-of-two count of inputs."""
    with qtrace.span("granule.merge", inputs=len(outputs)):
        n = merge_inputs(len(outputs))
        first = outputs[0]
        dead = None
        if n > len(outputs):
            dead = first.with_mask(jax.device_put(
                np.zeros(first.capacity, np.bool_)))
        tables = {_PARTIAL.format(i):
                  outputs[i] if i < len(outputs) else dead
                  for i in range(n)}
        return pp.execute_plan(gp.merge_plan(n), tables)


def execute_streamed(plan: pp.PlanNode, chunk_provider,
                     chunk_rows: int = DEFAULT_CHUNK_ROWS,
                     types: dict | None = None,
                     device_tables: dict | None = None,
                     table: str | None = None,
                     budget_bytes: int | None = None) -> Relation:
    """Run ``plan`` by streaming ONE table in fixed-size granules; every
    other table it names is in ``device_tables``, whole.

    chunk_provider(table_name, chunk_rows, bounds[, names]) -> iterator of
    ({col -> numpy array | CodedStrings}, {col -> valid or None}) host
    granules of at most ``chunk_rows`` rows (``segment_chunk_provider``,
    ``numpy_chunk_provider``).  The granules' outputs stay on the device
    and merge there: for a group-by whose state does not fit that way,
    see ``exec/spill_exec.py``."""
    chunk_rows = snap_chunk_rows(chunk_rows)
    if table is None:
        table = _find_single_scan(split_top(plan)[2])
    gp = GranulePlan(plan, table, chunk_rows)
    outputs = list(stream_outputs(gp, chunk_provider, device_tables or {},
                                  types, budget_bytes))
    return merge_outputs(gp, outputs)


def execute_sorted_streamed(
    plan: pp.PlanNode, chunk_provider, spill_dir: str,
    chunk_rows: int = DEFAULT_CHUNK_ROWS,
    budget_rows: int = 1 << 22, types: dict | None = None,
    disk_budget=None, faults=None, label: str = "",
):
    """ORDER BY over a table larger than host memory: granules filter on
    device, live rows drain to host, and the external merge sort
    (exec/external_sort.py) spills runs to ``spill_dir``.  A Limit above
    the Sort stops the merge as soon as offset+k rows have emerged —
    the tail of the merged stream is never read off disk.

    Supported shape: [Project?] [Limit?] Sort over a single-table
    scan/filter/project subtree with plain column sort keys.
    -> (arrays, valids) of the final (sorted, limited) host columns."""
    from oceanbase_tpu.exec import spill_exec

    top, scalar_agg, droot = split_top(plan)
    if scalar_agg is not None or isinstance(droot, pp.GroupBy):
        raise NotDistributable("sorted streaming is for scan pipelines")
    if not any(isinstance(n, pp.Sort) for n in top):
        raise NotDistributable("no Sort to stream")
    table = _find_single_scan(droot)
    arrays, valids, _dtypes, _stats = spill_exec.execute_spilled(
        plan, {table: chunk_provider}, spill_dir, budget_rows,
        types_by_table={table: types} if types else None,
        chunk_rows=chunk_rows, disk_budget=disk_budget, faults=faults,
        label=label).host()
    return arrays, valids


# ---------------------------------------------------------------------------
# providers
# ---------------------------------------------------------------------------


def numpy_chunk_provider(arrays: dict, valids: dict | None = None):
    """Granules from in-memory numpy columns (a relation without a tablet:
    external / transient).  String columns are factorised once, here."""
    from oceanbase_tpu.vector.column import factorize_strings

    cols, dicts = {}, {}
    for k, v in arrays.items():
        v = np.asarray(v)
        if v.dtype == object or v.dtype.kind in "US":
            codes, values = factorize_strings(v)
            cols[k] = CodedStrings(codes, values)
            dicts[k] = StringDict(values)
        else:
            cols[k] = v

    def provider(table, chunk_rows, bounds=None, names=None):
        n = len(next(iter(cols.values())))
        keep = [k for k in cols if names is None or k in names]
        for s in range(0, n, chunk_rows):
            e = min(s + chunk_rows, n)
            yield ({k: cols[k][s:e] for k in keep},
                   {k: (v[s:e] if v is not None else None)
                    for k, v in (valids or {}).items() if k in keep})

    provider.takes_names = True
    provider.columns = list(cols)
    provider.string_dicts = lambda names=None: {
        k: d for k, d in dicts.items() if names is None or k in names}
    provider.valid_columns = lambda names=None: [
        k for k, v in (valids or {}).items()
        if v is not None and (names is None or k in names)]
    return provider


def _segment_strings(seg, col: str):
    """-> (the sorted distinct strings of ``col`` in ``seg``, per chunk the
    int32 table from the chunk's own codes to positions in them; ``None``
    for a chunk stored plain).  Computed once a segment: it is immutable."""
    key = ("strings", col)
    got = seg.cache.get(key)
    if got is None:
        chunks = seg.columns[col]
        parts = [np.asarray(ec.payload["values"], object)
                 if ec.encoding == "sdict"
                 else np.asarray(ec.payload["data"], object)
                 for ec in chunks if ec.n]
        values = np.unique(np.concatenate(parts)) if parts \
            else np.zeros(0, object)
        luts = [np.searchsorted(values, ec.payload["values"])
                .astype(np.int32) if ec.encoding == "sdict" else None
                for ec in chunks]
        got = seg.cache[key] = (values, luts)
    return got


def _keys_unique(seg, key_cols) -> bool:
    """Whether no two rows of ``seg`` (key-sorted, as every segment is)
    share a key.  One pass over the key columns, once a segment."""
    key = ("keys_unique", tuple(key_cols))
    got = seg.cache.get(key)
    if got is None:
        got = True
        if seg.n_rows > 1 and key_cols != ["__rowid__"]:
            arrays, _ = seg.decode(names=[k for k in key_cols
                                          if k in seg.columns])
            same = np.ones(seg.n_rows - 1, dtype=bool)
            for a in arrays.values():
                same &= a[1:] == a[:-1]
            got = not bool(same.any())
        seg.cache[key] = got
    return got


def _key_index(key_arrays: list):
    """Key columns -> one pandas Index of row keys (hashable by value)."""
    import pandas as pd

    if len(key_arrays) == 1:
        return pd.Index(key_arrays[0])
    return pd.MultiIndex.from_arrays(key_arrays)


def segment_chunk_provider(tablet, snapshot: int):
    """Granules straight from the LSM, MVCC merged with array operations.

    What ``Tablet.snapshot_arrays`` reads at ``snapshot``, a granule at a
    time: of the rows with one key the newest wins, a tombstone hides its
    key.  The parts are the tablet's segments oldest first, then its
    memtables.  The OLDEST segment, when it is a direct load's (one row a
    key, no version or tombstone column, wholly visible), is the base: it
    streams from its chunks, zone maps pruning them, and a base row goes
    when a newer part holds its key.  Every newer part (a delta: flushed
    memtables, later loads, the memtables' visible rows) is decoded whole,
    the columns the plan reaches only, and merged among themselves by
    position (``_last_of_each_key``); it is never pruned, so that no
    version chain is split.  Where nothing newer than the base covers the
    snapshot no key column is decoded at all.

    A string column leaves as ``CodedStrings`` over the dictionary
    ``string_dicts`` gives (the segments' own sorted strings, merged), a
    gather through a per-chunk table: no row's string is touched."""
    from oceanbase_tpu.storage.tablet import _last_of_each_key, \
        _rows_to_arrays, _stack_parts

    key_cols = list(tablet.key_cols)
    types = tablet.types
    with tablet._lock:
        segs = [s for s in tablet.segments if s.min_version <= snapshot]
        mt_rows = [mt.snapshot_rows(snapshot)
                   for mt in tablet.memtables()[::-1]]
    mt_rows = [r for r in mt_rows if r]
    base = None
    if segs and "__version__" not in segs[0].columns \
            and "__deleted__" not in segs[0].columns \
            and segs[0].max_version <= snapshot \
            and _keys_unique(segs[0], key_cols):
        base, segs = segs[0], segs[1:]
    delta_cache: dict = {}

    def delta(names):
        """The parts newer than the base, merged -> (arrays, valids) of
        ``names`` for the rows that survive, and the keys of ALL their
        rows (what hides base rows)."""
        want = list(dict.fromkeys(list(names) + key_cols))
        k = tuple(want)
        if k in delta_cache:
            return delta_cache[k]
        parts = []
        for seg in segs:
            have = [c for c in want + ["__deleted__", "__version__"]
                    if c in seg.columns]
            a, v = seg.decode(names=have)
            if seg.max_version > snapshot and "__version__" in a:
                vis = a["__version__"] <= snapshot
                a = {c: x[vis] for c, x in a.items()}
                v = {c: (x[vis] if x is not None else None)
                     for c, x in v.items()}
            parts.append((a, v, None))
        for rows in mt_rows:
            a, v = _rows_to_arrays(rows, want, types)
            parts.append((a, v, None))
        if not parts:
            delta_cache[k] = (None, None, None)
            return delta_cache[k]
        arrays, valids = _stack_parts(parts, want, types)
        n = len(next(iter(arrays.values())))
        keep = np.ones(n, dtype=bool)
        keys = [arrays[c] for c in key_cols]
        if key_cols and n:
            keep = _last_of_each_key(keys)
        keep &= ~arrays["__deleted__"].astype(bool)
        out = ({c: arrays[c][keep] for c in names},
               {c: (valids[c][keep] if valids.get(c) is not None else None)
                for c in names},
               _key_index(keys) if key_cols and n and base is not None
               else None)
        delta_cache[k] = out
        return out

    def all_names(names):
        return list(names) if names is not None else list(tablet.columns)

    dict_cache: dict = {}

    def string_dicts(names=None):
        """{string column: the StringDict its granules' codes index}: the
        segments' own sorted strings and the memtable rows', merged."""
        out = {}
        for c in all_names(names):
            if not types[c].is_string:
                continue
            if c in dict_cache:
                out[c] = dict_cache[c]
                continue
            parts = [_segment_strings(s, c)[0]
                     for s in ([base] if base is not None else []) + segs
                     if c in s.columns]
            for rows in mt_rows:
                parts.append(np.array(
                    [v.values.get(c) or "" for v in rows.values()],
                    dtype=object))
            if len(parts) == 1 and base is not None:
                key = ("sdict", c)
                if key not in base.cache:
                    base.cache[key] = StringDict(parts[0])
                out[c] = base.cache[key]
            else:
                out[c] = StringDict(
                    np.unique(np.concatenate(parts)) if parts
                    else np.zeros(0, object))
            dict_cache[c] = out[c]
        return out

    def valid_columns(names=None):
        """The columns some part holds NULLs (or a validity array) for:
        every granule of the statement carries one for them."""
        names = all_names(names)
        if segs or mt_rows:
            return names    # a delta part gives every column one
        if base is None:
            return []
        return [c for c in names if c not in base.columns
                or any(ec.valid is not None for ec in base.columns[c])]

    def coded(strings: np.ndarray, sdict: StringDict) -> CodedStrings:
        return CodedStrings(
            np.searchsorted(sdict.values, strings).astype(np.int32),
            sdict.values)

    def provider(table, chunk_rows, bounds=None, names=None):
        names = all_names(names)
        dicts = string_dicts(names)
        has_valid = set(valid_columns(names))
        d_arrays, d_valids, d_keys = delta(names)

        def finish(arrays, valids, n):
            """Strings to codes, a validity array wherever the statement
            carries one."""
            for c in names:
                a = arrays[c]
                if types[c].is_string and not isinstance(a, CodedStrings):
                    arrays[c] = coded(a, dicts[c])
                if c in has_valid and valids.get(c) is None:
                    valids[c] = np.ones(n, dtype=bool)
            return arrays, valids

        if base is not None:
            mask = np.ones(base.n_chunks, dtype=bool)
            for col, (lo, hi) in (bounds or {}).items():
                if col in base.columns:
                    mask &= base.prune_chunks(col, lo, hi)
            provider.pruned_chunks += int((~mask).sum())
            sizes = [ec.n for ec in next(iter(base.columns.values()))]
            group: list = []
            rows = 0
            for i in list(np.flatnonzero(mask)) + [None]:
                if i is not None and rows + sizes[i] <= chunk_rows:
                    group.append(int(i))
                    rows += sizes[i]
                    continue
                if group:
                    yield from base_granules(group, rows, chunk_rows, names,
                                             dicts, d_keys, finish)
                if i is not None:
                    group, rows = [int(i)], sizes[i]
        if d_arrays is not None:
            n = len(next(iter(d_arrays.values()))) if d_arrays else 0
            for s in range(0, n, chunk_rows):
                e = min(s + chunk_rows, n)
                yield finish({c: a[s:e] for c, a in d_arrays.items()},
                             {c: (v[s:e] if v is not None else None)
                              for c, v in d_valids.items()}, e - s)

    def base_granules(group, rows, chunk_rows, names, dicts, d_keys, finish):
        """The base's chunks ``group`` (``rows`` rows) -> granules of at
        most ``chunk_rows``: decoded into one buffer a column, rows a
        newer part holds the key of taken out."""
        arrays, valids = {}, {}
        fetch = list(names)
        if d_keys is not None:
            fetch += [k for k in key_cols if k not in fetch]
        for c in fetch:
            t = types[c]
            if c not in base.columns:      # added after the load: NULLs
                arrays[c] = np.zeros(rows, dtype=np.int32 if t.is_string
                                     else t.np_dtype)
                if t.is_string:
                    arrays[c] = CodedStrings(arrays[c], dicts[c].values)
                valids[c] = np.zeros(rows, dtype=bool)
                continue
            chunks = base.columns[c]
            luts = _segment_strings(base, c)[1] if t.is_string else None
            if t.kind == TypeKind.VECTOR:
                from oceanbase_tpu.storage.encoding import decode_column

                arrays[c] = np.concatenate(
                    [decode_column(chunks[i]) for i in group])
                valids[c] = None
                continue
            out = np.empty(rows, dtype=np.int32 if t.is_string
                           else t.np_dtype)
            valid = np.ones(rows, dtype=bool) \
                if any(chunks[i].valid is not None for i in group) else None
            at = 0
            for i in group:
                ec = chunks[i]
                if luts is not None and luts[i] is None:
                    # a string chunk stored plain: its rows' strings
                    out[at:at + ec.n] = np.searchsorted(
                        dicts[c].values, ec.payload["data"])
                else:
                    decode_column_into(
                        ec, out[at:at + ec.n],
                        lut=None if luts is None else luts[i])
                if valid is not None and ec.valid is not None:
                    valid[at:at + ec.n] = ec.valid
                at += ec.n
            if luts is not None:
                vals = dicts[c].values
                seg_vals = _segment_strings(base, c)[0]
                if vals is not seg_vals and len(vals) != len(seg_vals):
                    # the table's dictionary holds newer parts' strings too
                    out = np.searchsorted(vals, seg_vals).astype(
                        np.int32)[out]
                arrays[c] = CodedStrings(out, vals)
            else:
                arrays[c] = out
            valids[c] = valid
        if d_keys is not None:
            keep = ~_key_index([
                arrays[k].strings() if isinstance(arrays[k], CodedStrings)
                else arrays[k] for k in key_cols]).isin(d_keys)
            if not keep.all():
                arrays = {c: a[keep] for c, a in arrays.items()}
                valids = {c: (v[keep] if v is not None else None)
                          for c, v in valids.items()}
                rows = int(keep.sum())
        arrays = {c: arrays[c] for c in names}
        for s in range(0, rows, chunk_rows):
            e = min(s + chunk_rows, rows)
            if s == 0 and e == rows:
                yield finish(arrays, {c: valids.get(c) for c in names}, rows)
            else:
                yield finish({c: a[s:e] for c, a in arrays.items()},
                             {c: (valids[c][s:e]
                                  if valids.get(c) is not None else None)
                              for c in names}, e - s)

    provider.takes_names = True
    provider.columns = list(tablet.columns)
    provider.pruned_chunks = 0
    provider.string_dicts = string_dicts
    provider.valid_columns = valid_columns
    return provider
