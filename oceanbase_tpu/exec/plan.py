"""Physical plan nodes + whole-plan compiler.

Reference analog: the ObOpSpec tree produced by the code generator
(ObStaticEngineCG, src/sql/code_generator/ob_static_engine_cg.h:188) and
driven by ObOperator::get_next_batch (src/sql/engine/ob_operator.cpp:1466).
The TPU build compiles the *entire* plan (or DFO fragment) into one XLA
program: plan nodes are specs; ``compile_plan`` lowers them to a pure
function {table -> Relation} -> Relation which is jitted and cached.

Operator profiling (≙ op_monitor_info_, src/sql/engine/ob_operator.cpp:1534)
hooks at this layer via the plan monitor (server/monitor.py).
"""

from __future__ import annotations

import copy
import functools
import hashlib
import re
import threading
import time
from collections import Counter
import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Sequence

import jax
import numpy as np

from oceanbase_tpu.exec import diag, ops
from oceanbase_tpu.exec.ops import AggSpec
from oceanbase_tpu.expr import ir
from oceanbase_tpu.expr.compile import (
    LUTS_TABLE,
    dictionary_luts,
    like_patterns,
    provided_luts,
)
from oceanbase_tpu.server import metrics as qmetrics
from oceanbase_tpu.server import trace as qtrace
from oceanbase_tpu.vector.column import Relation, prefetch

# device attribution + per-plan wall time (host-side, result boundary)
qmetrics.declare("plan.executions", "counter",
                 "execute_plan calls", )
qmetrics.declare("plan.join_probes", "counter",
                 "join / index probes executed, by how the program ranks "
                 "the probe keys (kind=merge|search: ops._probe_ranges "
                 "picks from the static shapes at trace time)")
qmetrics.declare("plan.groupby_reduces", "counter",
                 "group-bys executed, by the way the program takes "
                 "(kind=masked: dictionary / bool keys, no sort, masked "
                 "streaming reductions; kind=sort: sort + segment "
                 "reduce; ops.hash_groupby picks at trace time)")
qmetrics.declare("plan.groupby_segment_reduces", "counter",
                 "reductions of the sort-path group-bys executed over "
                 "their sorted lanes (the groups' own lanes and one an "
                 "aggregate), by the way taken (kind=scan: prefix sums "
                 "or a segmented scan read at the groups' end lanes; "
                 "kind=scatter: jax.ops.segment_* over the group "
                 "number, left to count_distinct; "
                 "ops.hash_groupby picks from the aggregate's "
                 "function and its argument's type at trace time)")
qmetrics.declare("plan.groupby_sorted_reads", "counter",
                 "reads of the sort-path group-bys executed of their "
                 "lanes in sorted order (the live flag and the keys "
                 "together one, and one an aggregate's distinct "
                 "argument), by where the lanes come from (kind=sort: "
                 "the group-by's own sort returns them, keys as its "
                 "outputs, an argument as a payload operand; "
                 "kind=gather: read through the sort's row numbers: an "
                 "argument that ops._rides_sort keeps off the sort, "
                 "too few lanes or too wide a sort for what an operand "
                 "costs the compiler, and count_distinct, which sorts "
                 "again)")
qmetrics.declare("plan.join_inputs", "counter",
                 "inputs of the joins and index probes executed, by "
                 "the lanes they arrive on (kind=compacted: densified "
                 "to the estimate's bucket by a Compact the planner "
                 "put under the join; kind=whole: on the lanes of what "
                 "lies under it; sql/optimizer.py::compact_join_input "
                 "decides at bind time)")
qmetrics.declare("plan.join_emits", "counter",
                 "joins executed that pair probe and build rows, by "
                 "where the pairs land (kind=probe_lanes: one lane per "
                 "probe lane, the build side unique on the key by a "
                 "declared primary key, HashJoin.build_unique; "
                 "kind=expanded: prefix sum + repeat into out_capacity "
                 "lanes; a semi / anti join on an exact key only masks "
                 "its probe and books neither)")
qmetrics.declare("plan.join_kinds", "counter",
                 "joins executed, by kind (how=inner|left|semi|anti|full: "
                 "one a join as ops.join lowers it, the exact-key semi / "
                 "anti joins that only mask their probe included)")
qmetrics.declare("plan.groupby_sort_lanes", "counter",
                 "lanes the sort-path group-bys executed handed their "
                 "sort (the input's static lanes, one note a group-by)")
qmetrics.declare("plan.groupby_out_lanes", "counter",
                 "static output lanes of the sort-path group-bys "
                 "executed (min(out_capacity, input lanes) each)")
qmetrics.declare("plan.groupby_groups", "counter",
                 "live groups the sort-path group-bys executed found (a "
                 "traced count read with the overflow total; over "
                 "plan.groupby_out_lanes: how full their outputs are)")
qmetrics.declare("plan.groupby_placements", "counter",
                 "group-bys executed that aggregate over an outer join's "
                 "NULL-supplying side, by where the planner put the "
                 "aggregation (at=below_join: under the join, over that "
                 "side's own lanes, GroupBy.below_join, its partials "
                 "combined above the join by a group-by that books "
                 "nothing; at=above_join: a GroupBy lowered directly "
                 "over a left HashJoin, through Projects, over the "
                 "lanes the join expands into; "
                 "sql/binder.py::_groupby_below_join decides at bind "
                 "time)")
qmetrics.declare("plan.compiles", "counter",
                 "XLA trace+compile events (per plan x input signature)")
qmetrics.declare("plan.capacity_retries", "counter",
                 "CapacityOverflow re-plans (the retry ladder the "
                 "cardinality-feedback store exists to shorten)")
qmetrics.declare("plan.feedback_hits", "counter",
                 "binds that found gv$plan_feedback rows for their "
                 "logical plan hash")
qmetrics.declare("plan.feedback_corrections", "counter",
                 "operator capacities raised at bind time from "
                 "observed cardinalities")
qmetrics.declare("plan.regressions", "counter",
                 "plan-regression watchdog flag transitions "
                 "(gv$plan_history.regressed going up)")
qmetrics.declare("plan.host_s", "histogram",
                 "host half of the execution split: bind + dispatch "
                 "until the runtime hands back futures", unit="s")
qmetrics.declare("plan.device_s", "histogram",
                 "device half of the execution split: "
                 "block_until_ready() bracketed at the result boundary "
                 "(the denominator of achieved_gflops)", unit="s")
qmetrics.declare("plan.sidecar_builds", "counter",
                 "index-probe sidecar rebuilds (argsort + pad) paid "
                 "because no cached sidecar matched the relation version")
qmetrics.declare("plan.sidecar_build_s", "histogram",
                 "wall time of one sidecar rebuild inside "
                 "prepare_index_probes", unit="s")


# ---------------------------------------------------------------------------
# plan-cache observability (≙ ObPlanCache stat views: gv$plan_cache)
# ---------------------------------------------------------------------------


@dataclass
class PlanCacheEntry:
    """Per-plan compile/execute counters surfaced by ``gv$plan_cache``.

    ``xla_traces`` counts XLA retrace events — the expensive part the
    shape-bucket policy amortizes; ``executions - xla_traces`` is the
    number of calls served entirely by an already-compiled executable.
    ``flops``/``bytes_accessed``/``peak_memory`` come from XLA's
    ``cost_analysis()``/``memory_analysis()`` on the most recently
    compiled signature — the measured statistics the cost-based
    optimizer arc prices against.  ``device_s_total`` accumulates the
    block_until_ready() half of the host/device split over
    ``device_executions`` timed runs, which makes ``achieved_gflops`` /
    ``achieved_gbps`` *measured* rates (program cost over measured
    device seconds), not datasheet numbers.
    """

    plan_hash: str            # stable digest of the plan fingerprint
    plan_text: str            # fingerprint prefix (human-readable)
    executions: int = 0       # execute_plan calls for this fingerprint
    xla_traces: int = 0       # trace (compile) events across all shapes
    last_compile_s: float = 0.0  # wall time of the last lower+compile
    last_lower_s: float = 0.0    # the Python-lowering share of it
    sidecar_builds: int = 0   # index-probe sidecar rebuilds for plans
    #                         # sharing this fingerprint
    sidecar_build_s: float = 0.0  # summed wall time of those rebuilds
    flops: float = 0.0        # cost_analysis flops (last compile)
    bytes_accessed: float = 0.0  # cost_analysis bytes (last compile)
    peak_memory: int = 0      # memory_analysis arg+temp+output bytes
    device_s_total: float = 0.0   # summed device half of timed runs
    host_s_total: float = 0.0     # summed host half (bind + dispatch)
    device_executions: int = 0    # runs with the time split enabled
    device_flops: float = 0.0     # flops behind the timed runs
    device_bytes: float = 0.0     # bytes-accessed behind the timed runs
    created_ts: float = field(default_factory=time.time)

    @property
    def hit_count(self) -> int:
        return max(self.executions - self.xla_traces, 0)

    @property
    def achieved_gflops(self) -> float:
        """Measured GFLOP/s over the timed executions (0.0 until one)."""
        if self.device_s_total <= 0.0:
            return 0.0
        return self.device_flops / self.device_s_total / 1e9

    @property
    def achieved_gbps(self) -> float:
        """Measured GB/s of bytes-accessed over the timed executions."""
        if self.device_s_total <= 0.0:
            return 0.0
        return self.device_bytes / self.device_s_total / 1e9


_PLAN_STATS: dict[str, PlanCacheEntry] = {}
_PLAN_STATS_LOCK = threading.Lock()
_PLAN_STATS_MAX = 4096


def _stats_for(key: str) -> PlanCacheEntry:
    # registry keyed by digest: full fingerprints are whole-plan reprs
    # (arbitrarily long) and must not be pinned per entry
    digest = hashlib.md5(key.encode()).hexdigest()
    with _PLAN_STATS_LOCK:
        e = _PLAN_STATS.get(digest)
        if e is None:
            if len(_PLAN_STATS) >= _PLAN_STATS_MAX:
                _PLAN_STATS.pop(next(iter(_PLAN_STATS)))
            e = PlanCacheEntry(plan_hash=digest, plan_text=key[:120])
            _PLAN_STATS[digest] = e
        return e


def plan_cache_stats() -> list[PlanCacheEntry]:
    """Snapshot of per-plan compile/execute counters (gv$plan_cache)."""
    with _PLAN_STATS_LOCK:
        return list(_PLAN_STATS.values())


def reset_plan_cache_stats():
    with _PLAN_STATS_LOCK:
        _PLAN_STATS.clear()


class PlanNode:
    """Immutable physical operator spec (≙ ObOpSpec)."""

    def children(self) -> Sequence["PlanNode"]:
        return ()

    def fingerprint(self) -> str:
        """Stable key for the plan cache."""
        return repr(self)


# Optimizer cardinality estimate riding every node (None = unknown).
# Excluded from repr/compare on purpose: the estimate is METADATA — two
# plans differing only in est_rows must share one fingerprint (and thus
# one compiled XLA executable); stats drifting as a table grows must
# never force a retrace.  The plan monitor pairs it with the measured
# output rows into the q-error ledger (gv$sql_plan_monitor).
def _est_field():
    return field(default=None, repr=False, compare=False)


def _marked_repr(node, mark: str) -> str:
    """``node``'s dataclass rendering with ``mark=True`` appended where the
    node carries the mark: an unmarked node renders as it did before the
    mark existed, so every plan that does not take the new path keeps its
    fingerprint (and with it gv$plan_cache's plan_hash and the AOT cache's
    key)."""
    parts = [f"{f.name}={getattr(node, f.name)!r}"
             for f in dataclasses.fields(node) if f.repr]
    if getattr(node, mark):
        parts.append(f"{mark}=True")
    return f"{type(node).__qualname__}({', '.join(parts)})"


@dataclass(repr=True)
class TableScan(PlanNode):
    table: str
    columns: Optional[list[str]] = None  # projection pushdown
    rename: Optional[dict[str, str]] = None  # output qualification
    est_rows: Optional[int] = _est_field()


@dataclass(repr=True)
class Filter(PlanNode):
    child: PlanNode
    pred: ir.Expr
    est_rows: Optional[int] = _est_field()

    def children(self):
        return (self.child,)


@dataclass(repr=True)
class Project(PlanNode):
    child: PlanNode
    outputs: dict  # name -> ir.Expr
    est_rows: Optional[int] = _est_field()

    def children(self):
        return (self.child,)


@dataclass(repr=True)
class GroupBy(PlanNode):
    child: PlanNode
    keys: dict  # name -> ir.Expr
    aggs: list  # list[AggSpec]
    out_capacity: Optional[int] = None
    est_rows: Optional[int] = _est_field()
    # the aggregation of an outer join's NULL-supplying side, planned
    # under the join by its join key (eager aggregation; sql/binder.py::
    # _groupby_below_join decides at bind time): unique on its one key
    # by construction
    below_join: bool = field(default=False, repr=False)

    def children(self):
        return (self.child,)

    def __repr__(self):
        return _marked_repr(self, "below_join")


@dataclass(repr=True)
class ScalarAgg(PlanNode):
    child: PlanNode
    aggs: list
    est_rows: Optional[int] = _est_field()

    def children(self):
        return (self.child,)


@dataclass(repr=True)
class HashJoin(PlanNode):
    left: PlanNode
    right: PlanNode
    left_keys: list
    right_keys: list
    how: str = "inner"
    out_capacity: Optional[int] = None
    est_rows: Optional[int] = _est_field()
    # the build (right) side is a filter chain over a scan and the one
    # join key its table's declared primary key, and the probe's lanes
    # fit the out_capacity: the join emits one lane per probe lane
    # (sql/optimizer.py::unique_build decides at bind time; ops.join
    # checks the guarantee at run time)
    build_unique: bool = field(default=False, repr=False)

    def children(self):
        return (self.left, self.right)

    def __repr__(self):
        return _marked_repr(self, "build_unique")


@dataclass(repr=True)
class SemiJoinResidual(PlanNode):
    """Semi/anti join with residual (non-equality correlated) predicates;
    out_capacity budgets the equality-expansion intermediate."""

    left: PlanNode
    right: PlanNode
    left_keys: list
    right_keys: list
    residual: list
    anti: bool = False
    out_capacity: Optional[int] = None
    est_rows: Optional[int] = _est_field()

    def children(self):
        return (self.left, self.right)


@dataclass(repr=True)
class IndexProbe(PlanNode):
    """Index nested-loop join: probe the child's key into a pre-sorted
    index sidecar of ``table`` and gather the matched base rows
    (≙ DAS index scan + table lookup, src/sql/das/iter — the NLJ access
    path the CBO picks when the probe side is far under the base table).

    The sidecar is a two-column relation (``__key__`` sorted int64,
    ``__pos__`` row positions into the base snapshot) the session builds
    host-side per data_version (sql/session.py::_prepare_index_probes)
    and injects under ``sidecar_name()``.  Output = child columns
    (expanded per match) + the base table's ``columns`` under
    ``rename`` — exactly a HashJoin's output, minus the build-side
    argsort every execution would pay."""

    child: PlanNode
    table: str
    index: str
    key: object          # ir.Expr over the child's columns
    columns: Optional[list[str]] = None
    rename: Optional[dict[str, str]] = None
    out_capacity: Optional[int] = None
    est_rows: Optional[int] = _est_field()

    def children(self):
        return (self.child,)

    @staticmethod
    def sidecar_name(table: str, index: str) -> str:
        return f"__probe__{table}__{index}"


@dataclass(repr=True)
class Window(PlanNode):
    """Window functions: adds result columns (≙ the window-function op,
    src/sql/engine/window_function)."""

    child: PlanNode
    specs: list  # list[(out_colid, ir.WindowCall)]
    est_rows: Optional[int] = _est_field()

    def children(self):
        return (self.child,)


@dataclass(repr=True)
class Union(PlanNode):
    """UNION ALL (concat); distinct layered via GroupBy above."""

    inputs: list
    est_rows: Optional[int] = _est_field()

    def children(self):
        return tuple(self.inputs)


@dataclass(repr=True)
class Sort(PlanNode):
    child: PlanNode
    keys: list
    ascending: Optional[list] = None
    est_rows: Optional[int] = _est_field()

    def children(self):
        return (self.child,)


@dataclass(repr=True)
class Limit(PlanNode):
    child: PlanNode
    k: int
    offset: int = 0
    est_rows: Optional[int] = _est_field()

    def children(self):
        return (self.child,)


@dataclass(repr=True)
class Compact(PlanNode):
    """Explicit cardinality-reduction point (densify live rows).

    ``strict`` surfaces rows beyond ``capacity`` on the overflow lane
    (executor retry) instead of silently truncating — mandatory when the
    Compact feeds an aggregate."""

    child: PlanNode
    capacity: Optional[int] = None
    strict: bool = False
    est_rows: Optional[int] = _est_field()

    def children(self):
        return (self.child,)


# ---------------------------------------------------------------------------
# plan-quality metadata: logical hash + estimate propagation
# ---------------------------------------------------------------------------


def _logical_repr(node: PlanNode) -> str:
    """Capacity-insensitive rendering: two plans that differ only in
    their static budgets (out_capacity scaling after CapacityOverflow)
    or estimates render identically — the key the cardinality-feedback
    store and the plan-regression watchdog aggregate on."""
    parts = []
    for k, v in vars(node).items():
        if k in ("out_capacity", "capacity", "est_rows") or \
                k.startswith("_") or (
                    k in ("build_unique", "below_join") and not v):
            continue
        if isinstance(v, PlanNode) or k in ("child", "left", "right",
                                            "inputs"):
            continue
        if isinstance(v, str) and k in ("table", "index", "name"):
            # hex-protect object identifiers: the colid normalization
            # below strips ``_<digits>`` suffixes, which would conflate
            # events_2024 and events_2025 into ONE feedback/history key
            # (capacity corrections and regression baselines would leak
            # across distinct tables); hex output contains no
            # underscores, so the regex cannot touch it
            parts.append(f"{k}={v.encode().hex()}")
            continue
        parts.append(f"{k}={v!r}")
    kids = ",".join(_logical_repr(c) for c in node.children())
    return f"{type(node).__name__}({','.join(parts)})[{kids}]"


_COLID_SEQ = re.compile(r"_\d+\b")


def logical_hash(node: PlanNode) -> str:
    """Stable digest of the plan MINUS capacities/estimates: the
    gv$plan_feedback / gv$plan_history key (a capacity retry or a stats
    refresh must not open a fresh history).

    Binder colids embed a session-global counter (``a_k_5``, ``o_9``),
    so the raw repr would hash differently on every rebind of the same
    statement — the counter suffixes are normalized away.  Table/index
    identifiers are hex-protected in _logical_repr so distinct tables
    never share a key; a string LITERAL ending in ``_<digits>`` still
    normalizes (worst case: two same-shaped predicates share one
    history, and apply_feedback's op-name check guards corrections).

    Memoized on the node (plans are treated as immutable once built;
    cached plans would otherwise pay the whole-tree render + digest on
    every execution)."""
    h = node.__dict__.get("_logical_hash")
    if h is None:
        text = _COLID_SEQ.sub("", _logical_repr(node))
        h = hashlib.md5(text.encode()).hexdigest()[:16]
        node.__dict__["_logical_hash"] = h
    return h


def propagate_estimates(node: PlanNode,
                        row_counts: dict | None = None) -> PlanNode:
    """Fill missing ``est_rows`` from the children (post-bind pass): the
    binder annotates the nodes it has real estimates for; everything
    else inherits a defensible bound so EVERY operator row in
    gv$sql_plan_monitor carries an estimate to q-error against.
    ``row_counts`` maps table -> live rows for un-annotated scans."""

    kids: dict = {}
    changed = False
    for fname in ("child", "left", "right"):
        if hasattr(node, fname):
            old = getattr(node, fname)
            nv = propagate_estimates(old, row_counts)
            kids[fname] = nv
            changed = changed or nv is not old
    if hasattr(node, "inputs"):
        nv_list = [propagate_estimates(c, row_counts)
                   for c in node.inputs]
        kids["inputs"] = nv_list
        changed = changed or any(a is not b for a, b in
                                 zip(nv_list, node.inputs))
    est = node.est_rows
    if est is None:
        if isinstance(node, TableScan):
            est = (row_counts or {}).get(node.table)
        elif isinstance(node, ScalarAgg):
            est = 1
        elif isinstance(node, Limit):
            ce = kids["child"].est_rows
            k = node.k + (node.offset or 0)
            est = k if ce is None else min(k, ce)
        elif isinstance(node, Union):
            subs = [c.est_rows for c in kids["inputs"]]
            known = [s for s in subs if s is not None]
            est = sum(known) if known else None
        elif isinstance(node, (HashJoin, SemiJoinResidual)):
            le = kids["left"].est_rows
            re_ = kids["right"].est_rows
            known = [v for v in (le, re_) if v is not None]
            est = max(known) if known else None
        elif "child" in kids:
            # single-child pass-through (Filter/Project/Sort/Window/
            # Compact/GroupBy without a binder estimate): the child's
            # cardinality is an upper bound
            est = kids["child"].est_rows
    if est is not None:
        est = max(int(est), 1)
    if est == node.est_rows and not changed:
        return node
    updates = dict(kids)
    if est != node.est_rows:
        updates["est_rows"] = est
    return dataclasses.replace(node, **updates)


def q_error(est: int | None, act: int) -> float:
    """Symmetric misestimate factor max(est/act, act/est), >= 1.0
    (0.0 = no estimate to compare).  The CBO literature's q-error."""
    if est is None:
        return 0.0
    e = max(float(est), 1.0)
    a = max(float(act), 1.0)
    return max(e / a, a / e)


# ---------------------------------------------------------------------------
# lowering
# ---------------------------------------------------------------------------


# pass-through operators preserve cardinality exactly (their output
# rows ≡ the child's), so a monitor lane on them would duplicate the
# child's ledger row while paying a real per-lane count inside the
# fused program — the ≤2% monitoring-overhead contract's biggest lever
PASSTHROUGH_OPS = ("Project", "Sort", "Compact", "Window")


def monitored_op(node: PlanNode, parent: "PlanNode | None" = None) -> bool:
    """Does this operator get its own estimate-vs-actual ledger row?

    Pass-through operators never do.  An inner Filter of a conjunct
    chain doesn't either: only the TOPMOST filter's output cardinality
    reaches the rest of the plan, and the binder splits one WHERE into
    a Filter per conjunct — monitoring each would pay one mask
    reduction per conjunct for rows that duplicate the chain head's."""
    if type(node).__name__ in PASSTHROUGH_OPS:
        return False
    return not (isinstance(node, Filter) and isinstance(parent, Filter))


def monitored_postorder(node: PlanNode,
                        parent: "PlanNode | None" = None) -> list:
    """The plan nodes that emit monitor lanes, in executor postorder —
    1:1 with a monitored execution's op_stats rows."""
    out = []
    for c in node.children():
        out.extend(monitored_postorder(c, node))
    if monitored_op(node, parent):
        out.append(node)
    return out


def _postorder(node: PlanNode) -> list:
    out = []
    for c in node.children():
        out.extend(_postorder(c))
    out.append(node)
    return out


# id(node) -> postorder position, for the duration of one root's lowering
_scope_pos = threading.local()


def _lower(node: PlanNode, tables: dict[str, Relation],
           parent: "PlanNode | None" = None) -> Relation:
    # every operator lowers under the scope "<Type>#<postorder position>"
    # (children nest inside: the INNERMOST scope of a device op is its
    # operator).  A scope is HLO metadata only — the plan fingerprint,
    # the AOT signature and the persistent cache's keys do not see it —
    # and it is what lets a profile say "HashJoin#5/join.probe" where it
    # used to say fusion.168
    pos = getattr(_scope_pos, "pos", None)
    root = pos is None or id(node) not in pos
    if root:
        saved = pos
        pos = _scope_pos.pos = {
            id(n): k for k, n in enumerate(_postorder(node))}
    try:
        with jax.named_scope(f"{type(node).__name__}#{pos[id(node)]}"):
            rel = _lower_inner(node, tables)
    finally:
        if root:
            _scope_pos.pos = saved
    # per-operator row accounting (no-op unless a monitor is collecting);
    # the optimizer's static estimate rides along host-side so the
    # monitor can q-error it against the measured count
    if monitored_op(node, parent):
        diag.monitor_push(type(node).__name__, rel.count(),
                          est=node.est_rows)
    return rel


def note_join_inputs(node: PlanNode) -> None:
    """One note per input of a join being lowered (serial or PX): did the
    planner compact it to its estimate's bucket, or does the join run
    over the lanes of what lies under it."""
    for child in node.children():
        diag.note("join_input", "compacted" if isinstance(child, Compact)
                  else "whole")


def note_groupby_placement(node: GroupBy) -> None:
    """One note for a group-by being lowered (serial or PX) that
    aggregates over an outer join's NULL-supplying side: ``below_join``
    where the planner pushed it under the join (the node carries the
    mark), ``above_join`` where it lies directly over a left join
    (through projections) and so groups the lanes the join expands into.
    The group-by that combines a pushed one's partials above the join is
    the same aggregation's second half and books nothing."""
    if node.below_join:
        diag.note("groupby_placement", "below_join")
        return
    join = _under_projects(node.child)
    if node.aggs and isinstance(join, HashJoin) and join.how == "left":
        build = _under_projects(join.right)
        if not (isinstance(build, GroupBy) and build.below_join):
            diag.note("groupby_placement", "above_join")


def _under_projects(node: PlanNode) -> PlanNode:
    while isinstance(node, Project):
        node = node.child
    return node


def _lower_inner(node: PlanNode, tables: dict[str, Relation]) -> Relation:
    if isinstance(node, TableScan):
        rel = tables[node.table]
        if node.columns is not None:
            rel = rel.select(node.columns)
        if node.rename:
            rel = Relation(
                columns={node.rename.get(n, n): c for n, c in rel.columns.items()},
                mask=rel.mask,
            )
        return rel
    if isinstance(node, Filter):
        return ops.filter_rows(_lower(node.child, tables, node),
                               node.pred)
    if isinstance(node, Project):
        return ops.project(_lower(node.child, tables, node),
                           node.outputs)
    if isinstance(node, GroupBy):
        note_groupby_placement(node)
        return ops.hash_groupby(
            _lower(node.child, tables, node), node.keys, node.aggs,
            out_capacity=node.out_capacity,
        )
    if isinstance(node, ScalarAgg):
        return ops.scalar_agg(_lower(node.child, tables, node),
                              node.aggs)
    if isinstance(node, HashJoin):
        note_join_inputs(node)
        return ops.join(
            _lower(node.left, tables, node),
            _lower(node.right, tables, node),
            node.left_keys, node.right_keys, how=node.how,
            out_capacity=node.out_capacity,
            build_unique=node.build_unique,
        )
    if isinstance(node, IndexProbe):
        note_join_inputs(node)
        return ops.index_probe(
            _lower(node.child, tables, node),
            tables[IndexProbe.sidecar_name(node.table, node.index)],
            tables[node.table], node.key, node.columns, node.rename,
            out_capacity=node.out_capacity,
        )
    if isinstance(node, SemiJoinResidual):
        return ops.semi_join_residual(
            _lower(node.left, tables, node),
            _lower(node.right, tables, node),
            node.left_keys, node.right_keys, node.residual,
            anti=node.anti, out_capacity=node.out_capacity,
        )
    if isinstance(node, Union):
        return ops.concat([_lower(c, tables, node)
                           for c in node.inputs])
    if isinstance(node, Window):
        from oceanbase_tpu.exec.window import window as window_op

        return window_op(_lower(node.child, tables, node), node.specs)
    if isinstance(node, Sort):
        return ops.sort_rows(_lower(node.child, tables, node),
                             node.keys, node.ascending)
    if isinstance(node, Limit):
        child = node.child
        if (isinstance(child, Sort) and node.offset == 0
                and node.k <= 4096 and len(child.keys) == 1):
            # fused top-N (single key; dictionary codes are order-preserving
            # so string keys qualify too): the Sort never lowers, so its
            # child's monitor lane parents to the Limit
            asc = child.ascending[0] if child.ascending else True
            return ops.top_n(_lower(child.child, tables, node),
                             child.keys[0], asc, node.k)
        return ops.limit(_lower(node.child, tables, node), node.k,
                         node.offset)
    if isinstance(node, Compact):
        return ops.compact(_lower(node.child, tables, node),
                           node.capacity, strict=node.strict)
    raise NotImplementedError(type(node).__name__)


def referenced_tables(node: PlanNode) -> set[str]:
    out = set()
    if isinstance(node, TableScan):
        out.add(node.table)
    if isinstance(node, IndexProbe):
        # the base table only: the sidecar is session-injected, not a
        # catalog table the snapshot builder could resolve
        out.add(node.table)
    for c in node.children():
        out |= referenced_tables(c)
    return out


def _narrows(node: PlanNode) -> bool:
    """Whether every column of ``node``'s output is named by a node of
    its subtree (Project, GroupBy, ScalarAgg define their outputs; the
    rest pass their inputs' columns through)."""
    if isinstance(node, (Project, GroupBy, ScalarAgg)):
        return True
    kids = node.children()
    return bool(kids) and all(_narrows(c) for c in kids)


def _strings(x, out: set) -> None:
    """Every string anywhere inside a plan: column ids in expressions, key
    lists and output maps among them.  A scan's ``rename`` is left out:
    it lists every column whether anything reads it or not."""
    if isinstance(x, str):
        out.add(x)
    elif isinstance(x, dict):
        for k, v in x.items():
            _strings(k, out)
            _strings(v, out)
    elif isinstance(x, (list, tuple, set, frozenset)):
        for v in x:
            _strings(v, out)
    elif isinstance(x, (TableScan, IndexProbe)):
        for f, v in vars(x).items():
            if f != "rename":
                _strings(v, out)
    elif hasattr(x, "__dict__"):
        _strings(vars(x), out)
    elif hasattr(x, "__slots__"):
        for f in x.__slots__:
            _strings(getattr(x, f, None), out)


def scan_columns(plan: PlanNode):
    """Which columns of its tables a plan can reach -> (names mentioned
    anywhere in the plan, {table: the renames of its scans}); ``None``
    where some column may pass through to the output unnamed.  A table
    behind an IndexProbe keeps every column.  The executable is handed
    the columns this names and no others: what a plan never reads (a
    string column whose dictionary a commit grew) is no part of its input
    signature, so it does not compile again."""
    if not _narrows(plan):
        return None
    mentioned: set = set()
    _strings(plan, mentioned)
    renames: dict = {}
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, TableScan):
            got = renames.setdefault(node.table, [])
            if got is not None and node.columns is None:
                got.append(node.rename or {})
            else:
                renames[node.table] = None
        elif isinstance(node, IndexProbe):
            renames[node.table] = None
        stack.extend(node.children())
    return mentioned, renames


def narrowed(rel: Relation, mentioned: set, renames) -> Relation:
    """``rel`` with the columns some scan hands on under a mentioned
    name; one column at least (the lanes' count rides on it)."""
    if renames is None:
        return rel
    keep = {n: c for n, c in rel.columns.items()
            if any(r.get(n, n) in mentioned for r in renames)}
    if len(keep) == len(rel.columns):
        return rel
    if not keep:
        n = min(rel.columns, key=lambda n: (rel.columns[n].sdict is not None,
                                            n))
        keep = {n: rel.columns[n]}
    return Relation(columns=keep, mask=rel.mask)


def prepare_index_probes(catalog, plan: PlanNode,
                         tables: dict[str, Relation]) -> None:
    """Host-build (and cache) the sorted index sidecar every IndexProbe
    in ``plan`` reads, injecting it into ``tables`` in place: ``__key__``
    the base table's index column over its LIVE valid rows, stably
    sorted and padded to the bucket ladder with _INT_MAX; ``__pos__``
    the matching positions into the base relation.  Cached on the
    catalog keyed by the SOURCE Relation's identity (snapshot relations
    are cached per version, so identity IS the data version; the entry
    keeps the relation alive against id recycling) — the argsort a hash
    join pays on every execution is paid here once per table version.

    Every executor entry point that lowers a plan must call this (or
    have its caller do so): session execution, bind-time scalar-subquery
    folding, px fragment lowering."""
    import numpy as np

    from oceanbase_tpu.datatypes import SqlType
    from oceanbase_tpu.exec.ops import _INT_MAX
    from oceanbase_tpu.vector import Column, bucket_capacity

    probes = []
    stack = [plan]
    while stack:
        node = stack.pop()
        if isinstance(node, IndexProbe):
            probes.append(node)
        stack.extend(node.children())
    if not probes:
        return
    cache = getattr(catalog, "_probe_cache", None)
    if cache is None:
        cache = catalog._probe_cache = {}
    for node in probes:
        sname = IndexProbe.sidecar_name(node.table, node.index)
        rel = tables.get(node.table)
        if rel is None:
            continue  # missing base table fails in _lower, not here
        ckey = (node.table, node.index)
        hit = cache.get(ckey)
        if hit is not None and hit[0] == id(rel):
            tables[sname] = hit[1]
            continue
        # cache miss: the rebuild below re-pays the argsort + pad every
        # hash join amortizes away — ROADMAP #1's per-session churn —
        # so it is timed into the statement's sidecar_build_s phase and
        # counted per plan fingerprint (gv$plan_cache.sidecar_builds)
        tb = time.perf_counter()
        td = catalog.table_def(node.table)
        ix = next(i for i in td.indexes if i.name == node.index)
        base_col = ix.columns[0]
        col = rel.columns[base_col]
        kd = np.asarray(col.data).astype(np.int64)
        valid = (np.ones(len(kd), dtype=bool) if col.valid is None
                 else np.asarray(col.valid))
        live = valid if rel.mask is None \
            else (valid & np.asarray(rel.mask))
        pos = np.nonzero(live)[0]
        keys = kd[pos]
        order = np.argsort(keys, kind="stable")
        keys, pos = keys[order], pos[order]
        n = len(keys)
        cap = bucket_capacity(max(n, 1))
        pk = np.full(cap, _INT_MAX, dtype=np.int64)
        ppos = np.zeros(cap, dtype=np.int64)
        pk[:n] = keys
        ppos[:n] = pos
        import jax.numpy as jnp

        sidecar = Relation(
            columns={
                "__key__": Column(jnp.asarray(pk), None,
                                  SqlType.int_()),
                "__pos__": Column(jnp.asarray(ppos), None,
                                  SqlType.int_())},
            mask=None)
        cache[ckey] = (id(rel), sidecar, rel)
        tables[sname] = sidecar
        dt = time.perf_counter() - tb
        st = _stats_for(plan.fingerprint())
        st.sidecar_builds += 1
        st.sidecar_build_s += dt
        qmetrics.inc("plan.sidecar_builds", table=node.table)
        qmetrics.observe("plan.sidecar_build_s", dt, table=node.table)
        # the statement's sidecar_build_s, not the `tables` span's too
        qtrace.book_owned("sidecar_build_s", int(dt * 1e9))


def _input_signature(tables: dict[str, Relation],
                     placed: bool = False) -> tuple:
    """Hashable signature equivalent to jit's dispatch key for a
    {name -> Relation} input: table/column names, leaf shapes + dtypes
    (+ weak_type), validity/mask presence, and the static aux metadata
    (SqlType, content-hashed StringDict).  Two inputs with equal
    signatures lower to the same XLA program; a cheaper hand-rolled walk
    than ``jax.tree_util.tree_flatten`` + abstractify on the hot path.
    ``placed``: the program runs over a mesh, so where a table lies is
    part of the key too (a compiled executable refuses inputs whose
    shardings differ from those it was lowered for); one sharding a
    table, as every PX input is placed whole by one call."""
    parts = []
    for tname in sorted(tables):
        rel = tables[tname]
        m = rel.mask
        p: list = [tname,
                   None if m is None else (m.shape, str(m.dtype))]
        cols = rel.columns
        for cname in sorted(cols):
            c = cols[cname]
            v = c.valid
            d = c.data
            p.append((cname, d.shape, str(d.dtype),
                      bool(getattr(d, "weak_type", False)),
                      None if v is None else (v.shape, str(v.dtype)),
                      c.dtype, c.sdict))
        if placed and cols:
            p.append(d.sharding)  # the last column's stands for all
        parts.append(tuple(p))
    return tuple(parts)


def _xla_analysis(exe) -> tuple[float, float, int]:
    """-> (flops, bytes_accessed, peak_memory_bytes) from the compiled
    executable's cost/memory analysis; zeros where a backend does not
    report (attribution degrades, execution never does)."""
    flops = nbytes = 0.0
    peak = 0
    try:
        ca = exe.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = max(float(ca.get("flops", 0.0)), 0.0)
        nbytes = max(float(ca.get("bytes accessed", 0.0)), 0.0)
    except Exception:  # noqa: BLE001 — backend-dependent surface
        pass
    try:
        ma = exe.memory_analysis()
        if ma is not None:
            peak = int(getattr(ma, "argument_size_in_bytes", 0)
                       + getattr(ma, "output_size_in_bytes", 0)
                       + getattr(ma, "temp_size_in_bytes", 0)
                       + getattr(ma, "generated_code_size_in_bytes", 0))
    except Exception:  # noqa: BLE001
        pass
    return flops, nbytes, peak


class Program:
    """What an executable is traced from, and the key it is cached under.

    ``body(*args, tables) -> Relation`` is the function to trace: the
    serial lowering of a plan (``_lower``), or one shard's half of a PX
    plan (``px/planner.py``).  ``shard`` = (mesh, axis, table names[, the
    names of those every shard holds whole]) wraps the traced function in
    ``jax.shard_map`` over the mesh, every other table on ``P(axis)``, the
    result relation on ``P(axis)`` and its overflow and count lanes as one
    vector ``psum``med over the axis.  ``stats_key`` names the program's
    ``gv$plan_cache`` row.  Only ``key`` is hashed and compared: the
    rest rides along to the cache miss that builds the executable."""

    __slots__ = ("body", "args", "key", "stats_key", "shard")

    def __init__(self, body, args: tuple, key, stats_key: str,
                 shard: tuple | None = None):
        self.body = body
        self.args = args
        self.key = key
        self.stats_key = stats_key
        self.shard = shard

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, Program) and other.key == self.key


class _PlanExecutable:
    """AOT compile cache for one (program, monitor flag), serial or PX:
    explicit ``lower().compile()`` per input signature instead of jit's
    implicit dispatch, so every compile event is observed exactly once —
    counted, timed, and cost/memory-attributed — with no second
    compilation to pay for the analysis.
    """

    MAX_SIGNATURES = 64  # >> the bucket-ladder rungs a table ever visits

    __slots__ = ("program", "stats", "diag_names", "monitor_names",
                 "count_names", "_noted", "_run", "_execs", "_lock",
                 "scan_columns", "like_patterns")

    def __init__(self, program: Program, with_monitor: bool = False):
        self.program = program
        #: a serial plan's ``scan_columns`` (execute_plan narrows its
        #: tables by it); None: the tables go in whole
        self.scan_columns = scan_columns(program.args[0]) \
            if program.body is _lower else None
        #: the plan's LIKE patterns: over a large dictionary each one's
        #: lookup table is an input of the program (``call``)
        self.like_patterns = frozenset(like_patterns(program.args))
        self.stats = _stats_for(program.stats_key)
        self.diag_names: list[str] = []     # filled at trace time
        self.monitor_names: list[str] = []
        #: the program's count lanes: (kind, row bytes) of each
        self.count_names: list[tuple] = []
        self._noted: Counter = Counter()    # the last trace's notes
        last_noted = self._noted
        body, args, shard = program.body, program.args, program.shard
        if with_monitor and shard:
            # its per-operator counts would be one shard's
            raise ValueError("a shard program carries no monitor lanes")
        diag_names = self.diag_names
        monitor_names = self.monitor_names
        count_names = self.count_names

        def run(tables):
            with diag.collect() as entries, diag.note_collect() as noted, \
                    diag.count_collect() as counted, \
                    provided_luts(tables.get(LUTS_TABLE)):
                if with_monitor:
                    with diag.monitor_collect() as mons:
                        out = body(*args, tables)
                    monitor_names.clear()
                    # (op name, static est) pairs; only the count lane
                    # is traced
                    monitor_names.extend((n, e) for n, e, _ in mons)
                    mvals = [v for _, _, v in mons]
                else:
                    out = body(*args, tables)
                    mvals = []
                import jax.numpy as _jnp

                # ONE stacked vector instead of N scalars: the host
                # reads all per-op counts in a single device transfer
                # (N blocking syncs per execution would dominate the
                # monitoring overhead budget)
                mon_vec = (_jnp.stack([_jnp.asarray(v, dtype=_jnp.int64)
                                       for v in mvals])
                           if mvals else _jnp.zeros((0,), _jnp.int64))
            diag_names.clear()
            # (lane name, static capacity) pairs for the overflow report
            diag_names.extend((n, cap) for n, _, cap in entries)
            last_noted.clear()
            for what, value, n in noted:
                last_noted[what, value] += n
            # fold the per-operator overflow lanes into ONE scalar on
            # device: the per-execute host check reads a single value
            # instead of syncing once per diagnostic lane (obcheck
            # trace.host-sync)
            import jax.numpy as jnp

            total = jnp.zeros((), dtype=jnp.int64)
            for _n, v, _cap in entries:
                total = total + jnp.maximum(
                    jnp.asarray(v, dtype=jnp.int64), 0)
            lanes = [v for _, v, _ in entries]
            count_names.clear()
            count_names.extend((k, b) for k, b, _ in counted)
            counts = [jnp.maximum(jnp.asarray(c, dtype=jnp.int64), 0)
                      for _, _, c in counted]
            if shard is not None:
                # one vector summed over the mesh: every overflow lane
                # (a lane's detail on one shard would be that shard's),
                # then every count lane; the host reads it once
                vec = [jnp.maximum(jnp.asarray(v, dtype=jnp.int64), 0)
                       for v in lanes] + counts
                lanes = jax.lax.psum(
                    jnp.stack(vec) if vec else jnp.zeros((0,), jnp.int64),
                    shard[1])
                total = jnp.sum(lanes[:len(entries)])
            elif counts:
                # the one value the host reads at every execution, with
                # the count lanes behind it: [overflow total, counts...]
                # (a program that counts nothing keeps its scalar)
                total = jnp.stack([total] + counts)
            return out, lanes, total, mon_vec

        if shard is not None:
            from jax.sharding import PartitionSpec as P

            mesh, axis, names, *rest = shard
            whole = rest[0] if rest else ()     # tables every shard holds
            run = jax.shard_map(
                run, mesh=mesh,
                in_specs=({t: P() if t in whole else P(axis)
                           for t in names}
                          | ({LUTS_TABLE: P()} if self.like_patterns
                             else {}),),
                out_specs=(P(axis), P(), P(), P()), check_vma=False)
        # only ever driven through .lower()/.compile(): the jit wrapper
        # exists for the lowering machinery (and so obcheck keeps seeing
        # `run` as a traced root), its dispatch cache stays empty
        self._run = jax.jit(run)
        #: signature -> (compiled executable, flops, bytes, peak, notes
        #: of the trace by (what, value): the shapes of the signature
        #: pick each)
        self._execs: dict[tuple, tuple] = {}
        self._lock = threading.Lock()

    def _compile(self, tables, sig):
        # two windows, one total: lower() is the Python tracing half
        # (plan walk + jaxpr build), compile() the XLA backend half —
        # the time model attributes them separately (lower_s/compile_s)
        # while last_compile_s stays their sum for the existing
        # gv$plan_cache column.  This bracket is the ONE source of both
        # phases, serial and PX: JAX's own compile events inside it
        # count into jax.compile_ns but book nothing (bracketed_compile),
        # and a collector pause inside is gc_s, not lowering.
        acc = _exec_acc()
        st = self.stats
        with qtrace.span("xla.compile", plan_hash=st.plan_hash) as csp, \
                qtrace.bracketed_compile():
            g0 = acc.gc_s
            t0 = time.perf_counter()
            lowered = self._run.lower(tables)
            lower_s = time.perf_counter() - t0 - (acc.gc_s - g0)
            noted = Counter(self._noted)
            exe = lowered.compile()
            flops, nbytes, peak = _xla_analysis(exe)
            csp.tags.update(flops=flops, bytes_accessed=nbytes,
                            peak_memory=peak)
        acc.lower_s += lower_s
        acc.compile_s += max(csp.self_s - lower_s, 0.0)
        st.xla_traces += 1
        st.last_compile_s = csp.elapsed_s
        st.last_lower_s = lower_s
        st.flops = flops
        st.bytes_accessed = nbytes
        st.peak_memory = peak
        qmetrics.inc("plan.compiles")
        # the statement's wall time holds a compile: the plan-regression
        # watchdog leaves it out (reset_compile_flag)
        _exec_flags.compiled = True
        if len(self._execs) >= self.MAX_SIGNATURES:
            self._execs.pop(next(iter(self._execs)))
        entry = (exe, flops, nbytes, peak, noted)
        self._execs[sig] = entry
        return entry

    def call(self, tables):
        """-> ((out, diag_vals, diag_total, mon_vals), compiled_now,
        flops, bytes_accessed, noted) — the cost-analysis pair and the
        trace's notes are the executed SIGNATURE's, so callers can
        attribute measured device time to the program that actually
        ran and book what it noted (``diag.book_notes``)."""
        if self.like_patterns:
            shard = self.program.shard
            tables = {**tables, LUTS_TABLE: dictionary_luts(
                self.like_patterns, tables, shard[0] if shard else None)}
        sig = _input_signature(tables, self.program.shard is not None)
        entry = self._execs.get(sig)
        compiled_now = False
        if entry is None:
            with self._lock:
                entry = self._execs.get(sig)
                if entry is None:
                    entry = self._compile(tables, sig)
                    compiled_now = True
        exe, flops, nbytes, _peak, noted = entry
        return exe(tables), compiled_now, flops, nbytes, noted


# per-thread statement-scoped compile marker: the session resets it
# before a statement's retry ladder and the plan-regression watchdog
# skips samples whose wall time includes an XLA compile (or a retry
# replay) — otherwise the warmup baseline freezes at compile-inflated
# latency and real steady-state regressions never cross the threshold
_exec_flags = threading.local()


def reset_compile_flag():
    _exec_flags.compiled = False


def compile_flag() -> bool:
    """Did any plan compilation happen on this thread since the last
    reset_compile_flag()?"""
    return bool(getattr(_exec_flags, "compiled", False))


# ---------------------------------------------------------------------------
# host/device time split (the roofline-calibration plane's measurement
# half): when enabled, execute_plan brackets ``block_until_ready()`` at
# the existing result boundary so every execution records host_s (bind +
# dispatch until the runtime hands back futures) and device_s (the wait
# for the computation itself) separately.  Process-global like the
# metrics enable flag; Database wires it to ``enable_profiling``.
# ---------------------------------------------------------------------------

_TIME_SPLIT = True


def set_time_split(on: bool):
    global _TIME_SPLIT
    _TIME_SPLIT = bool(on)


def time_split_enabled() -> bool:
    return _TIME_SPLIT


class ExecTimes:
    """Per-statement execution accounting, accumulated across every
    execute_plan call (retries, granule chunks, spill sub-plans) plus
    remote DTL fragments folded in via ``add_exec_times``.  ``flops`` /
    ``bytes`` are the XLA cost_analysis totals of the executed programs
    — the numerators the roofline prediction prices against ``calls``
    launches of measured ``device_s``.

    The named phases (``PHASES``, server/trace.py) decompose the host
    half — the gv$sql_audit columns and gv$time_model rows.  Most are
    the SELF time of the span of that boundary (``trace.PHASE_OF``:
    ``parse`` -> ``parse_s`` ... ``materialize`` -> ``materialize_s``),
    booked when the span closes; ``sidecar_build_s``, and ``lower_s`` /
    ``compile_s`` of a plan program (serial or PX: the executable's AOT
    bracket), are bracketed by their owner; ``trace_s`` /
    ``cache_lookup_s`` (and ``lower_s`` / ``compile_s`` wherever jit
    dispatch compiles implicitly: eager ops, the chunk programs of the
    spill tier) come from JAX's own compile events; ``gc_s`` from the
    collector's callbacks.  Whoever books time inside an open span
    charges it to that span as child time, so no second is owned twice
    and ``elapsed_s - queue_s - phase_sum()`` is the unowned rest
    (``other_s``).  ``close_s`` is the statement's work after its root
    span closed (metrics, trace retention, the audit row): outside
    ``elapsed_s``.  ``host_s`` stays the legacy aggregate (local
    dispatch + remote fragments' host halves).

    A plain class whose fields default at CLASS level: a statement's
    fresh accumulator is an empty instance (the session makes one per
    statement), and only what a statement books becomes an instance
    attribute."""

    host_s = device_s = flops = bytes = 0.0
    calls = 0
    bind_s = sidecar_build_s = lower_s = compile_s = dispatch_s = 0.0
    merge_s = parse_s = admission_s = virtuals_s = prepare_s = 0.0
    tables_s = device_copy_s = trace_s = cache_lookup_s = shard_s = 0.0
    delta_apply_s = dml_s = tx_commit_s = log_sync_s = freeze_s = 0.0
    unshard_s = monitor_s = record_s = materialize_s = gc_s = 0.0
    close_s = 0.0

    def __repr__(self):
        return f"ExecTimes({vars(self)})"

    #: the host-phase decomposition, in pipeline order (shared by
    #: gv$sql_audit columns, gv$time_model rows and the report builder)
    PHASES = qtrace.PHASES

    def phase_sum(self) -> float:
        """Sum of the named host phases + device_s — what the
        time-model-sums-to-wall reconciliation compares against the
        audited statement elapsed."""
        return sum(getattr(self, p) for p in self.PHASES) + self.device_s

    def worst_phase(self) -> tuple[str, float]:
        """(phase name, seconds) of the dominant host phase — the
        EXPLAIN ANALYZE roofline callout."""
        name = max(self.PHASES, key=lambda p: getattr(self, p))
        return name, getattr(self, name)


def _exec_acc() -> ExecTimes:
    acc = qtrace.statement_times()
    if acc is None:
        acc = ExecTimes()
        qtrace.set_statement_times(acc)
    return acc


def reset_exec_times() -> ExecTimes:
    """Statement start: the session clears the accumulator alongside
    reset_compile_flag().  -> the fresh accumulator: the statement's
    audit row keeps it, so what closes after the row was written
    (``close_s``) still lands in it.  It lives in the tracer's
    per-thread state, where a closing span books its self time."""
    acc = ExecTimes()
    qtrace.set_statement_times(acc)
    return acc


def exec_times() -> ExecTimes:
    """Snapshot of this thread's statement-scoped accumulator."""
    return copy.copy(_exec_acc())


def add_exec_times(**seconds):
    """Fold externally measured work into the statement accumulator —
    DTL coordinators merge the split their remote fragments shipped
    back, so a pushed-down statement's device_s covers the cluster.
    Keywords are ``ExecTimes`` fields (prepare_index_probes books
    sidecar_build_s, the DTL coordinator merge_s)."""
    for name, v in seconds.items():
        _book_phase(name, v)


def _book_phase(name: str, seconds: float):
    acc = _exec_acc()
    setattr(acc, name, getattr(acc, name) + seconds)


@functools.lru_cache(maxsize=256)
def executable_for(program: Program,
                   with_monitor: bool = False) -> _PlanExecutable:
    """THE executable cache, serial and PX plans alike (≙ ObPlanCache).
    The stats object rides along with the executable: callers must count
    executions on the same one (a fresh _stats_for lookup could return a
    new entry after registry eviction and desync the counters)."""
    return _PlanExecutable(program, with_monitor)


def execute_plan(plan: PlanNode, tables: dict[str, Relation],
                 check_overflow: bool = True,
                 monitor_out: list | None = None,
                 monitor_collect: bool = True) -> Relation:
    """Compile (cached) + run a plan against device tables.

    ≙ ObExecutor::execute_plan (src/sql/executor/ob_executor.cpp:37); the
    compilation cache here is the engine-level analog of the plan cache
    (ObPlanCache::get_plan, src/sql/plan_cache/ob_plan_cache.cpp:579).

    ``monitor_out`` selects the executable VARIANT (with/without monitor
    lanes) — it must be stable per plan across executions or the plan
    compiles twice and breaks the shape-bucket compile-count invariant.
    ``monitor_collect`` is the cheap per-execution sampling switch: when
    False the lanes still run on device (same executable) but the host
    skips the transfer and the ledger rows.

    Raises diag.CapacityOverflow when any static-capacity operator
    (join expansion, exchange buffer) overflowed — results would be
    silently truncated otherwise; the caller re-plans with larger budgets.
    """
    # cancel/deadline checkpoint (server/admission.py): host-side, at
    # the plan boundary only — never inside the jit-traced body, so
    # KILL/query_timeout_s observe here without touching compile keys
    from oceanbase_tpu.server import admission as qadmission

    qadmission.checkpoint()
    with_monitor = monitor_out is not None
    # full-link trace: one HOST-side span per plan execution with one
    # child per phase, each closed at a result boundary below (never
    # inside the jit-traced `run` body).  The children's self times are
    # the statement's dispatch_s / device_s / monitor_s (trace.PHASE_OF)
    with qtrace.span("plan.execute") as tsp:
        with qtrace.span("plan.dispatch") as dsp:
            key = plan.fingerprint()
            needed = referenced_tables(plan)
            # IndexProbe sidecars are session-injected relations, not
            # catalog tables — referenced_tables() deliberately omits
            # them (its other callers resolve names against the
            # catalog), so re-add them here or the filter below would
            # strip the probe's sorted-key input
            stack = [plan]
            while stack:
                n = stack.pop()
                if isinstance(n, IndexProbe):
                    needed.add(IndexProbe.sidecar_name(n.table, n.index))
                stack.extend(n.children())
            bundle = executable_for(Program(_lower, (plan,), key, key),
                                    with_monitor)
            stats = bundle.stats
            given = {k: v for k, v in tables.items() if k in needed}
            if bundle.scan_columns is not None:
                mentioned, renames = bundle.scan_columns
                given = {k: narrowed(v, mentioned, renames.get(k))
                         for k, v in given.items()}
            (out, diag_vals, diag_total, mon_vals), compiled_now, flops, \
                nbytes, noted = bundle.call(given)
            stats.executions += 1
            # what the host will read is asked for now, behind the
            # program, so that it arrives with the wait's end instead of
            # one round trip each after it: a small result's arrays
            # (to_numpy finds them there), the overflow total, a sampled
            # execution's counts
            prefetch(out)
            if check_overflow and (diag_vals or bundle.count_names):
                diag_total.copy_to_host_async()
            if with_monitor and monitor_collect:
                mon_vals.copy_to_host_async()
        # a first execution at a signature pays lower()+compile() inside
        # the window above as the xla.compile child span: the dispatch
        # span's SELF time is the per-execution dispatch, and the
        # one-time cost does not read as a dispatch stall in
        # gv$sql_audit.host_s — the same exclusion the PR 8 plan-history
        # watchdog applies to its latency baselines
        host_s = dsp.self_s
        diag_names = bundle.diag_names
        monitor_names = bundle.monitor_names
        root_op = type(plan).__name__
        tsp.tags["plan_hash"] = stats.plan_hash
        device_s = 0.0
        if _TIME_SPLIT:
            # the host/device split: dispatch returned futures above;
            # waiting for one HERE (host side, result boundary — the
            # same place the overflow check would sync anyway) makes
            # device_s the computation's own time, not host dispatch.
            # Blocking ONE output scalar suffices: the plan runs as a
            # single fused program whose output buffers all fulfill at
            # completion — and keeps the split's cost O(1), not
            # O(output tree) (the <=2% profile_bench budget).
            with qtrace.span("plan.device_wait") as wsp:
                jax.block_until_ready(  # obcheck: ok(trace.host-sync)
                    diag_total)
            device_s = wsp.elapsed_s
        # the execution's books — cache stats, histograms and, when this
        # execution is sampled, the per-operator counts — are one span,
        # so that what follows the wait has a name on the timeline
        with qtrace.span("plan.monitor"):
            if _TIME_SPLIT:
                stats.device_s_total += device_s
                stats.host_s_total += host_s
                stats.device_executions += 1
                stats.device_flops += flops
                stats.device_bytes += nbytes
                qmetrics.observe("plan.host_s", host_s, op=root_op)
                qmetrics.observe("plan.device_s", device_s, op=root_op)
                tsp.tags["host_s"] = round(host_s, 6)
                tsp.tags["device_s"] = round(device_s, 6)
            acc = _exec_acc()
            acc.host_s += host_s
            acc.flops += flops
            acc.bytes += nbytes
            acc.calls += 1
            plan_elapsed = dsp.elapsed_s + device_s
            qmetrics.inc("plan.executions", op=root_op)
            diag.book_notes(noted)
            if compiled_now:
                tsp.tags["compiled"] = 1
            if with_monitor and monitor_collect:
                # audited: opt-in plan-monitor collection materializes
                # per-op row counts; only with enable_sql_plan_monitor
                # set.  Each row is the estimate-vs-actual ledger entry:
                # the binder's est_rows beside the measured output rows
                # with their q-error (gv$sql_plan_monitor row shape).
                import numpy as _np

                # audited result-boundary sync: ONE transfer
                # materializes every per-op count
                mon_host = _np.asarray(mon_vals)  # obcheck: ok(trace.host-sync)
                # estimates come from the CURRENT plan, not the ones the
                # cached executable captured at trace time: the compile
                # cache keys on fingerprint() (est-insensitive by
                # design), so after ANALYZE / table growth a re-bound
                # plan reuses the executable but must report its own
                # refreshed est_rows
                live = monitored_postorder(plan)
                ests = ([n.est_rows for n in live]
                        if len(live) == len(monitor_names)
                        else [e for _, e in monitor_names])
                op_rows = []
                for i, ((n, _tr_est), v) in enumerate(
                        zip(monitor_names, mon_host)):
                    est = ests[i]
                    act = int(v)
                    op_rows.append({"op": n, "pos": i, "est": est,
                                    "rows": act,
                                    "q_error": q_error(est, act),
                                    "elapsed_s": 0.0})
                if op_rows:
                    # the plan runs as ONE fused XLA program, so per-op
                    # wall time is not separable; the root carries the
                    # plan total
                    op_rows[-1]["elapsed_s"] = plan_elapsed
                monitor_out.extend(op_rows)
        if check_overflow and (diag_vals or bundle.count_names):
            with qtrace.span("plan.overflow_check"):
                # audited result-boundary sync: ONE host read decides
                # validity and brings the count lanes; the per-lane
                # detail below only materializes on the error path
                head = np.asarray(  # obcheck: ok(trace.host-sync)
                    diag_total).reshape(-1)
                total = int(head[0])
                if total == 0:
                    diag.book_counts(bundle.count_names, head[1:])
                else:
                    vals = [int(v) for v in diag_vals]  # obcheck: ok(trace.host-sync)
                    drops = [(n, cap, v)
                             for (n, cap), v in zip(diag_names, vals)
                             if v > 0]
                    detail = ", ".join(f"{n}={v}" for n, _cap, v in drops)
                    raise diag.CapacityOverflow(
                        f"operator capacity exceeded ({detail} rows "
                        f"dropped); re-plan with larger out_capacity",
                        drops=drops,
                    )
    # operator-close checkpoint: a killed/expired statement unwinds at
    # the result boundary instead of riding out the rest of the plan
    qadmission.checkpoint()
    return out
