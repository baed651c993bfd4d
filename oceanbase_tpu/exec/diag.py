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
# join-probe lane: which way each probe of the program ranks its keys
# (ops._probe_ranges picks from static shapes, so the kinds are a fact of
# the traced program, known when lowering ends; nothing is traced).
# ---------------------------------------------------------------------------

_probes: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "ob_tpu_probes", default=None
)


def probe_collect():
    """Activate the lane; yields the list of kinds, in program order."""
    return _collecting(_probes)


def note_probe(kind: str) -> None:
    """Record one probe's kind, ``merge`` or ``search`` (no-op outside a
    collector)."""
    kinds = _probes.get()
    if kinds is not None:
        kinds.append(kind)


# ---------------------------------------------------------------------------
# group-by lane: which way each group-by of the program took
# (ops.hash_groupby picks from the keys' static types and code space: a
# fact of the traced program like the probe kinds above).
# ---------------------------------------------------------------------------

_groupbys: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "ob_tpu_groupbys", default=None
)


def groupby_collect():
    """Activate the lane; yields the list of kinds, in program order."""
    return _collecting(_groupbys)


def note_groupby(kind: str) -> None:
    """Record one group-by's kind: ``masked`` (dictionary / bool keys, no
    sort, masked streaming reductions) or ``sort`` (no-op outside a
    collector)."""
    kinds = _groupbys.get()
    if kinds is not None:
        kinds.append(kind)


# ---------------------------------------------------------------------------
# PX lane: what the distributed lowering decided (a join's distribution
# method, an exchange buffer's static capacity).  Facts of the traced
# shard program like the probe kinds above; the executable keeps them and
# every execution adds them to ``gv$sysstat``.
# ---------------------------------------------------------------------------

_px_notes: contextvars.ContextVar[list | None] = contextvars.ContextVar(
    "ob_tpu_px_notes", default=None
)


def px_collect():
    """Activate the lane; yields the list of (what, value, n): a join by
    its distribution method, an exchange buffer's lanes by its kind."""
    return _collecting(_px_notes)


def _note_px(what: str, value: str, n: int) -> None:
    notes = _px_notes.get()
    if notes is not None:
        notes.append((what, value, n))


def note_join(dist: str) -> None:
    """One join of the program being lowered, by distribution method
    (no-op outside a collector)."""
    _note_px("join", dist, 1)


def note_lanes(kind: str, lanes: int) -> None:
    """One exchange buffer of ``lanes`` lanes a shard, by kind."""
    _note_px("lanes", kind, lanes)
