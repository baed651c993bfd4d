"""Execution diagnostics lane: overflow accounting under jit.

XLA programs can't raise, so data-dependent failures (static-capacity
overflow in joins/exchanges — SURVEY §7 hard part (a)) are accumulated as
traced scalars into an active collector during lowering; the executor
bundles them into the compiled function's outputs and checks them on the
host after the run, failing loudly instead of returning truncated results.

Reference analog: the defensive result checks the reference compiles in
(ENABLE_SANITY expr-output checker, src/sql/engine/ob_operator.cpp:1556)
plus DTL flow-control backpressure (src/sql/dtl/ob_dtl_flow_control.h) —
which on TPU becomes "detect that the static buffer budget was exceeded
and re-plan with larger capacity".
"""

from __future__ import annotations

import contextlib
import contextvars

from oceanbase_tpu.server import metrics as qmetrics

_collector: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "ob_tpu_diag", default=None
)


@contextlib.contextmanager
def _collecting(var: contextvars.ContextVar):
    """Activate ``var`` with a fresh list; yields the list."""
    entries: list = []
    tok = var.set(entries)
    try:
        yield entries
    finally:
        var.reset(tok)


def collect():
    """Activate a collector; yields the list that traced entries land in."""
    return _collecting(_collector)


def push(name: str, scalar, capacity: int | None = None) -> None:
    """Record a traced overflow scalar (no-op outside a collector).

    ``capacity`` is the STATIC budget of the operator that pushed the
    lane (known at trace time): the executor pairs it with the dropped
    count so a CapacityOverflow can report how big the budget should
    have been — the cardinality-feedback plane's overflow-time signal.
    """
    entries = _collector.get()
    if entries is not None:
        entries.append((name, scalar, capacity))


class CapacityOverflow(RuntimeError):
    """Raised by the executor when an operator exceeded its static
    capacity; callers re-plan with a larger budget (spill in later rounds).

    ``drops`` holds ``(lane_name, static_capacity_or_None, rows_dropped)``
    per overflowing diagnostic lane, so the retry path can jump straight
    to a sufficient budget instead of blindly riding the 4x ladder."""

    def __init__(self, msg: str, drops: list | None = None):
        super().__init__(msg)
        self.drops = drops or []


# ---------------------------------------------------------------------------
# per-operator monitor lane (≙ op_monitor_info_ row counts,
# src/sql/engine/ob_operator.cpp:1534): operators report their live-row
# output as traced scalars bundled into the compiled plan's outputs.
# ---------------------------------------------------------------------------

_monitor: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "ob_tpu_monitor", default=None
)


def monitor_collect():
    return _collecting(_monitor)


def monitor_push(op_name: str, count_scalar, est: int | None = None) -> None:
    """Record one operator's live-row output scalar plus the optimizer's
    STATIC cardinality estimate for that operator (None = unknown) — the
    estimate rides host-side, only the count is traced."""
    entries = _monitor.get()
    if entries is not None:
        entries.append((op_name, est, count_scalar))


# ---------------------------------------------------------------------------
# count lane: what an execution MEASURES beside its result and is booked
# to ``gv$sysstat`` (the live rows a PX exchange received, by kind; the
# groups a sort-path group-by found).  The lanes leave the program in the
# vector the host reads for the overflow check anyway (a shard program's
# summed over the mesh): no sync of their own.
# ---------------------------------------------------------------------------

_counts: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "ob_tpu_counts", default=None
)


def count_collect():
    """Activate the lane; yields the list of (kind, row_bytes, scalar)."""
    return _collecting(_counts)


def count_rows(kind: str, row_bytes: int, scalar) -> None:
    """Record the traced live-row count of one exchange of ``kind`` whose
    rows are ``row_bytes`` wide (no-op outside a collector)."""
    entries = _counts.get()
    if entries is not None:
        entries.append((kind, row_bytes, scalar))


#: the ``kind`` under which a sort-path group-by counts its live groups
GROUPS = "groupby_groups"


def book_counts(names, values) -> int:
    """One execution's count lanes (``names``: (kind, row bytes) of each,
    ``values`` the host's copy): add each to its series.  -> the rows the
    exchanges among them received."""
    moved = 0
    for (kind, width), v in zip(names, values):
        if kind == GROUPS:
            qmetrics.inc("plan.groupby_groups", int(v))
            continue
        qmetrics.inc("px.exchange_rows", int(v), kind=kind)
        qmetrics.inc("px.exchange_bytes", int(v) * width, kind=kind)
        moved += int(v)
    return moved


# ---------------------------------------------------------------------------
# note lane: facts of the traced program that an operator picks from
# static shapes and types (which way a probe ranks its keys, which way a
# group-by reduces, and by scans or a scatter where it sorted, whether a
# join's input was compacted to its estimate's bucket, whether a join
# emits on its probe's lanes or expands, whether a group-by over an outer
# join's NULL-supplying side runs below the join or above it, how a PX
# join is distributed, an exchange buffer's lanes).
# Nothing is traced: the notes are known when lowering ends, the
# executable keeps their counts per input signature, and every execution
# adds them to ``gv$sysstat`` (``book_notes``).  A new operator's counter
# is a ``declare()`` of its series, a row here and a ``note()`` call.
# ---------------------------------------------------------------------------

#: what -> (series, label) the note's value is booked under
NOTE_SERIES = {
    "probe": ("plan.join_probes", "kind"),        # merge | search
    "groupby": ("plan.groupby_reduces", "kind"),  # masked | sort
    # (a sort-path group-by's reductions over its sorted lanes)
    "groupby_reduce": ("plan.groupby_segment_reduces",
                       "kind"),                   # scan | scatter
    # (where a sort-path group-by's sorted lanes come from: its own
    # sort's outputs and payloads, or gathers through its row numbers)
    "groupby_sorted_read": ("plan.groupby_sorted_reads",
                            "kind"),              # sort | gather
    "join_input": ("plan.join_inputs", "kind"),   # compacted | whole
    "join_emit": ("plan.join_emits", "kind"),   # probe_lanes | expanded
    "join_kind": ("plan.join_kinds", "how"),  # inner|left|semi|anti|full
    # (a sort-path group-by's lanes: n = what it sorts, what it emits on)
    "groupby_sort_lanes": ("plan.groupby_sort_lanes", None),
    "groupby_out_lanes": ("plan.groupby_out_lanes", None),
    # (a group-by over an outer join's NULL-supplying side: where the
    # planner put it)
    "groupby_placement": ("plan.groupby_placements",
                          "at"),               # below_join | above_join
    "join": ("px.joins", "dist"),    # partition_wise|broadcast|pkey|hash
    "lanes": ("px.exchange_lanes", "kind"),  # n = lanes a shard
}

_notes: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "ob_tpu_notes", default=None
)


def note_collect():
    """Activate the lane; yields the list of (what, value, n), in program
    order."""
    return _collecting(_notes)


def note(what: str, value: str, n: int = 1) -> None:
    """Record one fact of the program being lowered (no-op outside a
    collector); a ``what`` with no row in ``NOTE_SERIES`` raises here, at
    trace time, not at the first execution's booking."""
    if what not in NOTE_SERIES:
        raise KeyError(f"diag.note: no series for {what!r}")
    notes = _notes.get()
    if notes is not None:
        notes.append((what, value, n))


def book_notes(noted) -> None:
    """One execution of a program whose trace noted ``noted`` (a Counter
    of (what, value)): add each to its series."""
    for (what, value), n in noted.items():
        series, label = NOTE_SERIES[what]
        # a name out of the table above, each declare()d where its
        # operator lives  # obcheck: ok(metric.dynamic-name)
        qmetrics.inc(series, n, **({label: value} if label else {}))
