"""Full-link query tracing: one trace tree per statement across nodes.

Reference analog: the full-link trace (flt) — ObTrace/FLTSpanMgr
(deps/oblib/src/lib/trace/ob_trace.h, src/share/ob_ls_id rides spans
through the rpc frame) surfaced as ``SHOW TRACE`` and gv$ob_trace.  A
statement opens a ROOT span; every layer underneath (plan compile vs
execute, per-operator work, spill, DTL slice fan-out/merge, every rpc
verb) attaches children, and remote handlers continue the tree on their
node, shipping their spans back with the reply.  Completed traces land
in a bounded per-node ring served as ``gv$trace`` (+ ``SHOW TRACE`` for
the last statement, and a trace_id column joined into gv$sql_audit).

Design constraints (obcheck trace.* rules + the <=2% overhead budget of
scripts/trace_bench.py):

- spans are HOST-side only and close at the result boundary — nothing
  here may run inside jit-traced code or force a device sync;
- ONE clock: every span also enters a ``jax.profiler.TraceAnnotation``
  named ``ob:<name>``, so during a profiler capture the program's spans
  are events on the host plane of the same ``.xplane.pb`` as the device
  ops.  A TraceMe with no capture running costs ~0.3us, so it is entered
  unconditionally; with no statement context a span is the annotation
  (and its phase booking) alone;
- every span knows its SELF time (duration minus what its children,
  booked compile events and collector pauses cover): a span whose name
  is in ``PHASE_OF`` books it into the statement's ``ExecTimes`` (kept
  in this module's per-thread state), which is how gv$sql_audit and
  gv$time_model own every host phase;
- the statement path pays one object, two clock reads and the
  annotation per span, and nothing of it outlives the statement: at
  statement end the tree is packed into one flat tuple for the ring
  (``Span`` records are built when somebody reads), so the collector's
  young generations see one retained object a statement (the <=1.5%
  budget and the p99 of the benchmark's scan cell ride on this);
- collection is always cheap enough to run at sample_rate=1.0, so the
  ``trace_sample_rate`` / ``trace_slow_threshold_s`` knob pair decides
  RETENTION at statement end, not collection — which is how a query
  that only turned out slow (or failed) still has its full tree.

Timing hygiene: ``start_ts`` is a wall-clock record timestamp (the
context's wall start plus a monotonic offset: one clock read per span
boundary), ``elapsed_s`` is always a ``time.perf_counter_ns()`` delta
(step-proof).
"""

from __future__ import annotations

import collections
import itertools
import random
import threading
import time
from dataclasses import dataclass, field

from jax.profiler import TraceAnnotation

from oceanbase_tpu.server import metrics as qmetrics

__all__ = [
    "Span", "TraceCtx", "TraceRegistry", "span", "activate", "current",
    "current_span_id", "start_trace", "finish_trace", "add_span",
    "begin_span", "end_span", "absorb", "annotation", "book_owned",
    "ANNOTATION_PREFIX", "PHASE_OF", "PHASES", "SLOW_FACTOR",
    "bracketed_compile", "begin_statement", "install_runtime_hooks",
    "statement_times", "set_statement_times",
]

#: prefix of every annotation the program writes into a profiler capture
#: (NOT ``bench:`` — the benchmark's readers take those as their own)
ANNOTATION_PREFIX = "ob:"

#: span name -> ``ExecTimes`` field its SELF time is booked into (the
#: thread's accumulator, ``_tls.times``, is exec/plan.py's; names absent
#: here book nothing: their children do, or their owner books explicitly)
PHASE_OF = {
    "parse": "parse_s",
    "admission": "admission_s",
    "virtuals": "virtuals_s",
    "compile": "bind_s",
    "plan.prepare": "prepare_s",
    "tables": "tables_s",
    "storage.device_copy": "device_copy_s",
    "storage.delta_apply": "delta_apply_s",
    "storage.delta_read": "delta_apply_s",
    "plan.dispatch": "dispatch_s",
    "plan.device_wait": "device_s",
    "plan.monitor": "monitor_s",
    "plan.overflow_check": "monitor_s",
    "plan.record": "record_s",
    "materialize": "materialize_s",
    "statement.close": "close_s",
    "px.shard": "shard_s",
    "px.program": "dispatch_s",
    "px.unshard": "unshard_s",
    "px.merge": "merge_s",
    "px.device_wait": "device_s",
    # a streamed statement: a granule's chunk program (dispatch and the
    # wait for it), the merge of the partial states around its own plan
    # program; fetch and upload run on the producer thread and book none
    "granule.program": "device_s",
    "granule.merge": "merge_s",
    # the write path: a DML statement's own work, the commit, what the
    # commit waits for its replicated log, a foreground flush
    "dml.bind": "dml_s",
    "dml.match": "dml_s",
    "dml.candidates": "dml_s",
    "dml.predicate": "dml_s",
    "dml.assign": "dml_s",
    "dml.rows": "dml_s",
    "dml.write": "dml_s",
    "tx.commit": "tx_commit_s",
    "tx.log_encode": "tx_commit_s",
    "tx.apply": "tx_commit_s",
    "palf.append": "log_sync_s",
    "palf.persist": "log_sync_s",
    "palf.apply": "log_sync_s",
    "storage.freeze": "freeze_s",
}

#: the host phases of a statement in pipeline order: ``ExecTimes``
#: fields, gv$sql_audit columns and gv$time_model rows.  All lie inside
#: the audited ``elapsed_s`` (``close_s``, the work after the root span
#: closed, does not), so with ``queue_s`` and ``device_s`` they sum to it
#: up to ``other_s``
PHASES = ("parse_s", "admission_s", "virtuals_s", "bind_s", "prepare_s",
          "tables_s", "device_copy_s", "delta_apply_s", "sidecar_build_s",
          "trace_s",
          "lower_s", "compile_s", "cache_lookup_s", "dispatch_s",
          "shard_s", "unshard_s", "merge_s", "monitor_s", "record_s",
          "materialize_s", "dml_s", "tx_commit_s", "log_sync_s", "freeze_s",
          "gc_s")

#: spans that never ride an rpc reply (TraceCtx.wire_spans)
_NODE_LOCAL = frozenset(("plan.dispatch", "plan.device_wait", "plan.monitor",
                         "plan.overflow_check"))

#: a statement this many times over its plan_history baseline keeps its
#: tree in the slow ring whatever ``trace_slow_threshold_s`` says
SLOW_FACTOR = 8.0

_ANN_NAMES: dict[str, str] = {}


def annotation_name(name: str) -> str:
    full = _ANN_NAMES.get(name)
    if full is None:
        full = _ANN_NAMES[name] = ANNOTATION_PREFIX + name
    return full


def annotation(name: str) -> TraceAnnotation:
    """``ob:<name>`` on the profiler's timeline (not yet entered)."""
    return TraceAnnotation(annotation_name(name))


def charge_child(ns: int):
    """Time just spent inside the current span that has an owner of its
    own (a booked JAX compile event, a collector pause, a synthetic
    span): the span's SELF time must not hold it too."""
    top = _tls.top
    if top is not None:
        top._child_ns += ns


def statement_times():
    """This thread's statement accumulator (an ``ExecTimes``), or None."""
    return _tls.times


def set_statement_times(acc):
    _tls.times = acc


def book_owned(phase: str, ns: int):
    """``ns`` just spent on this thread belong to ``phase`` of its
    statement, and to no span."""
    charge_child(ns)
    acc = _tls.times
    if acc is not None:
        setattr(acc, phase, getattr(acc, phase) + ns * 1e-9)


#: process-wide span sequence: combined with the node id this makes span
#: ids unique across every context a node ever creates, so remote spans
#: merged into a coordinator tree can never collide
_SEQ = itertools.count(1)


class _Tls(threading.local):
    """Per-thread tracing state; the defaults live on the class, so a
    read is a plain attribute access on every thread."""

    ctx = None          # current TraceCtx
    parent = 0          # current parent span id
    top = None          # innermost open _SpanCM
    bracketed = 0       # depth of bracketed_compile()
    intervals = None    # booked compile events / pauses (_enclosed_ns)
    gc = None           # the collector pause in progress
    times = None        # the statement's ExecTimes (exec/plan.py owns it)


_tls = _Tls()


@dataclass
class Span:
    """One timed operation (≙ one ObTrace span / gv$ob_trace row)."""

    trace_id: str
    span_id: int
    parent_id: int
    node: int
    name: str
    start_ts: float            # wall clock (record timestamp)
    elapsed_s: float
    tags: dict = field(default_factory=dict)

    def to_wire(self) -> dict:
        """JSON-able shape riding the rpc codec unchanged."""
        return {"t": self.trace_id, "s": self.span_id, "p": self.parent_id,
                "n": self.node, "nm": self.name, "st": self.start_ts,
                "el": self.elapsed_s, "tg": self.tags or None}

    @staticmethod
    def from_wire(d: dict) -> "Span":
        return Span(d["t"], int(d["s"]), int(d["p"]), int(d["n"]),
                    d["nm"], float(d["st"]), float(d["el"]),
                    dict(d["tg"]) if d.get("tg") else {})


class TraceCtx:
    """Per-statement collection context (one per trace per node).

    Thread-safe append (``list.append`` is atomic): the DTL fan-out
    collects slice spans from worker threads into the coordinator's
    context.
    """

    __slots__ = ("trace_id", "node", "sampled", "slow_s", "slow", "spans",
                 "_wall0", "_ns0")

    def __init__(self, trace_id: str, node: int = 0, sampled: bool = True,
                 slow_s: float = float("inf")):
        self.trace_id = trace_id
        self.node = node
        self.sampled = sampled
        self.slow_s = slow_s
        self.slow = False   # set by the session: far over its baseline
        self.spans: list = []   # Span records and closed _SpanCMs
        self._wall0 = time.time()
        self._ns0 = time.perf_counter_ns()

    def wall_at(self, ns: int) -> float:
        """Record timestamp of a ``perf_counter_ns`` reading."""
        return self._wall0 + (ns - self._ns0) * 1e-9

    def next_id(self) -> int:
        return (self.node << 32) | next(_SEQ)

    def add(self, sp: Span):
        self.spans.append(sp)

    def wire_spans(self) -> list[dict]:
        """The spans an rpc reply ships back (``Span.to_wire`` shapes).
        The phases under ``plan.execute`` stay on the node that ran
        them — their times ride that span's ``host_s`` / ``device_s``
        tags and the reply's ``tm`` field, and a pushed-down fragment's
        reply is held to a few percent of the bytes it saves — and what
        hung under one of them (``xla.compile``) moves up to its parent."""
        spans = self.snapshot()
        up = {sp.span_id: sp.parent_id for sp in spans
              if sp.name in _NODE_LOCAL}
        out = []
        for sp in spans:
            if sp.span_id in up:
                continue
            d = sp.to_wire()
            while d["p"] in up:
                d["p"] = up[d["p"]]
            out.append(d)
        return out

    def snapshot(self) -> list[Span]:
        """The collected spans as records (a span closed by ``with`` is
        kept as its context manager until somebody reads)."""
        return [_record(sp) for sp in list(self.spans)]


class TraceRegistry:
    """Bounded per-node ring of completed span TREES (the gv$trace
    store), plus a small ring of whole SLOW trees that fast statements
    cannot evict: at sample rate 1.0 the ring holds the last few hundred
    statements, and the tree of a 4 s stall must outlive them.

    A tree is kept PACKED (``_pack``): one flat tuple of plain values
    for the whole statement, so retention leaves the collector one
    object a statement to look at, not one per span (the young
    generations' pauses are what the scan cell's p99 is made of); the
    ``Span`` rows are built when somebody reads (``recent`` /
    ``trace``).  The ring is bounded by ``max_spans`` spans in all,
    whole trees leaving from the old end."""

    SLOW_TREES = 64

    def __init__(self, max_spans: int = 20000):
        self._max_spans = max_spans
        self._trees: collections.deque = collections.deque()
        self._count = 0
        self._slow: collections.deque = collections.deque(
            maxlen=self.SLOW_TREES)
        self._lock = threading.Lock()
        self.traces_kept = 0
        self.traces_dropped = 0

    def add(self, spans: list, slow: bool = False):
        if not spans:
            return
        tree = _pack(spans)
        with self._lock:
            if slow:
                self._slow.append(tree)
            else:
                self._trees.append(tree)
                self._count += len(tree) // _WIDTH
                while self._count > self._max_spans and \
                        len(self._trees) > 1:
                    self._count -= len(self._trees.popleft()) // _WIDTH
            self.traces_kept += 1

    def note_dropped(self):
        with self._lock:
            self.traces_dropped += 1

    def recent(self, n: int | None = None) -> list[Span]:
        """Last ``n`` spans (``None`` = everything held): the slow trees
        first, then the ring."""
        with self._lock:
            trees = list(self._slow) + list(self._trees)
        out = [sp for tree in trees for sp in _unpack(tree)]
        return out if n is None else out[-n:]

    def trace(self, trace_id: str) -> list[Span]:
        with self._lock:
            trees = list(self._slow) + list(self._trees)
        return [sp for tree in trees if tree[0] == trace_id
                for sp in _unpack(tree)]

    def slow_trace_ids(self) -> list[str]:
        """Trace ids of the trees the slow ring holds, oldest first."""
        with self._lock:
            return [tree[0] for tree in self._slow]


def _record(sp) -> Span:
    return sp if isinstance(sp, Span) else sp.record()


#: values per span of a packed tree: the ``Span`` fields in order
_WIDTH = 8


def _pack(spans: list) -> tuple:
    """A collected tree (closed ``_SpanCM``s and absorbed ``Span``
    records) -> one flat tuple, ``_WIDTH`` plain values a span in
    ``Span``'s field order; ids are drawn here, once.  Runs at every
    statement's end: attribute reads only, no call per span."""
    flat = []
    for sp in spans:
        if type(sp) is _SpanCM:
            ctx = sp._ctx
            node = ctx.node
            sid = sp._sid
            if not sid:
                sid = sp._sid = (node << 32) | next(_SEQ)
            up = sp._up
            if up is not None and up._ctx is ctx:
                parent = up._sid
                if not parent:
                    parent = up._sid = (node << 32) | next(_SEQ)
            else:
                parent = sp._base
            flat += (ctx.trace_id, sid, parent, node, sp.name,
                     ctx._wall0 + (sp._t0 - ctx._ns0) * 1e-9,
                     sp._dur * 1e-9, sp.tags or None)
        else:
            flat += (sp.trace_id, sp.span_id, sp.parent_id, sp.node,
                     sp.name, sp.start_ts, sp.elapsed_s, sp.tags or None)
    return tuple(flat)


def _unpack(tree: tuple) -> list[Span]:
    return [Span(*tree[i:i + 7], dict(tree[i + 7] or ()))
            for i in range(0, len(tree), _WIDTH)]


# ---------------------------------------------------------------------------
# thread-local current context (+ explicit hand-off for worker threads)
# ---------------------------------------------------------------------------


def current() -> TraceCtx | None:
    return _tls.ctx


def current_span_id() -> int:
    """Id of the innermost open span of the current trace (what a span
    handed to another thread or node hangs under)."""
    top = _tls.top
    if top is not None and top._ctx is not None and top._ctx is _tls.ctx:
        return top.span_id
    return _tls.parent


class _Activate:
    """Install ``ctx`` (and a parent span id) as this thread's current
    trace; ``activate(None)`` is a no-op context manager so call sites
    need no branching."""

    __slots__ = ("_ctx", "_parent", "_saved")

    def __init__(self, ctx: TraceCtx | None, parent: int = 0):
        self._ctx = ctx
        self._parent = parent

    def __enter__(self):
        self._saved = (_tls.ctx, _tls.parent)
        if self._ctx is not None:
            _tls.ctx = self._ctx
            _tls.parent = self._parent
        return self._ctx

    def __exit__(self, et, ev, tb):
        _tls.ctx, _tls.parent = self._saved
        return False


def activate(ctx: TraceCtx | None, parent: int = 0) -> _Activate:
    return _Activate(ctx, parent)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class _SpanCM:
    """Class-based context manager (cheaper than @contextmanager): the
    span closes at ``with`` exit — by construction at the host result
    boundary, never per device lane.  ``_ctx`` may be None (no
    statement context): then it is the annotation and the phase
    booking alone.  After exit (not before) ``elapsed_s`` / ``self_s``
    hold what it measured, for owners that book explicitly.

    The statement path pays for one object, two clock reads and the
    annotation: the span's id is drawn when somebody asks for it or
    when the tree is packed for the ring at statement end."""

    __slots__ = ("_ctx", "name", "tags", "_t0", "_up", "_base",
                 "_child_ns", "_ann", "_dur", "_sid")

    def __init__(self, ctx: TraceCtx | None, name: str, tags: dict):
        self._ctx = ctx
        self.name = name
        self.tags = tags
        self._sid = 0

    def __enter__(self):
        # the annotation opens first and closes last: the span's own
        # bookkeeping is on the timeline as part of the span, not as an
        # unnamed gap beside it
        name = self.name
        self._ann = ann = TraceAnnotation(
            _ANN_NAMES.get(name) or annotation_name(name))
        ann.__enter__()
        tls = _tls
        self._up = up = tls.top
        if up is None or up._ctx is not self._ctx:
            self._base = tls.parent  # the root of its tree on this thread
        tls.top = self
        self._child_ns = 0
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, et, ev, tb):
        self._dur = dur = time.perf_counter_ns() - self._t0
        tls = _tls
        tls.top = up = self._up
        if up is not None:
            up._child_ns += dur
        phase = PHASE_OF.get(self.name)
        if phase is not None:
            acc = tls.times
            if acc is not None:
                setattr(acc, phase, getattr(acc, phase)
                        + (dur - self._child_ns) * 1e-9)
        if self._ctx is not None:
            if et is not None:
                self.tags.setdefault("error", et.__name__)
            self._ctx.spans.append(self)  # packed at statement end
        ann = self._ann
        self._ann = None
        ann.__exit__(et, ev, tb)
        return False

    @property
    def elapsed_s(self) -> float:
        return self._dur * 1e-9

    @property
    def self_s(self) -> float:
        return max(self._dur - self._child_ns, 0) * 1e-9

    def so_far_s(self) -> float:
        """Seconds since the span opened (for an owner that must report
        before it closes)."""
        return (time.perf_counter_ns() - self._t0) * 1e-9

    @property
    def trace_id(self) -> str:
        return self._ctx.trace_id

    @property
    def span_id(self) -> int:
        sid = self._sid
        if not sid:
            sid = self._sid = self._ctx.next_id()
        return sid

    def record(self) -> Span:
        ctx = self._ctx
        up = self._up
        parent = up.span_id if (up is not None and up._ctx is ctx) \
            else self._base
        return Span(ctx.trace_id, self.span_id, parent, ctx.node,
                    self.name, ctx.wall_at(self._t0), self._dur * 1e-9,
                    self.tags)


def span(name: str, **tags):
    """``with span("dtl.slice", part=3) as sp:`` — tags may be extended
    through ``sp.tags`` before close.  The only way to open a span: with
    no trace active it still marks the profiler's timeline and books its
    phase, and records nothing."""
    return _SpanCM(_tls.ctx, name, tags)


def add_span(name: str, elapsed_s: float, **tags):
    """Record a synthetic (already-measured) span under the current
    parent for time JUST spent inside the current span (the admission
    queue wait): the enclosing span's self time gives it up."""
    charge_child(int(elapsed_s * 1e9))
    ctx = _tls.ctx
    if ctx is None:
        return
    ctx.add(Span(ctx.trace_id, ctx.next_id(), current_span_id(),
                 ctx.node, name, time.time() - elapsed_s, float(elapsed_s),
                 tags))


# -- manual begin/end (rpc client wraps a retry loop, not a with-block) ----


class _OpenSpan:
    __slots__ = ("name", "tags", "span_id", "parent_id", "_t0", "_ann")


def begin_span(ctx: TraceCtx, name: str, parent: int, **tags) -> _OpenSpan:
    sp = _OpenSpan()
    sp.name = name
    sp.tags = tags
    sp.parent_id = parent
    sp.span_id = ctx.next_id()
    sp._ann = annotation(name)
    sp._ann.__enter__()
    sp._t0 = time.perf_counter_ns()
    return sp


def end_span(ctx: TraceCtx, sp: _OpenSpan):
    dur = time.perf_counter_ns() - sp._t0
    sp._ann.__exit__(None, None, None)
    ctx.add(Span(ctx.trace_id, sp.span_id, sp.parent_id, ctx.node,
                 sp.name, ctx.wall_at(sp._t0), dur * 1e-9, sp.tags))


# ---------------------------------------------------------------------------
# runtime events on the same books: JAX's compile events, the collector
# ---------------------------------------------------------------------------

qmetrics.declare("jax.compile_ns", "counter",
                 "self time of JAX's own compile events by stage (trace | "
                 "lower | backend | cache_lookup)", unit="ns")
qmetrics.declare("jax.compile_events", "counter",
                 "JAX trace / lower / backend-compile / cache-retrieval "
                 "events seen")
qmetrics.declare("runtime.gc_pause_ns", "counter",
                 "time the cyclic collector held a thread", unit="ns")
qmetrics.declare("runtime.gc_collections", "counter",
                 "collector runs by generation")

#: jax.monitoring time-span event -> (counter stage, ExecTimes field)
_JAX_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": ("trace", "trace_s"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lower", "lower_s"),
    "/jax/core/compile/backend_compile_duration": ("backend", "compile_s"),
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class bracketed_compile:
    """``with bracketed_compile():`` — the owner times ``lower()`` /
    ``compile()`` itself (exec/plan.py's executable), so JAX's events inside
    still count into ``jax.compile_ns`` but book no phase: one source
    per phase."""

    __slots__ = ()

    def __enter__(self):
        _tls.bracketed += 1

    def __exit__(self, et, ev, tb):
        _tls.bracketed -= 1
        return False


_MAX_INTERVALS = 8192


def _enclosed_ns(start: float, dur: int) -> int:
    """Book the interval that began at wall time ``start`` and lasted
    ``dur`` ns, and has just ended on this thread; -> the time of the
    earlier-booked intervals it encloses (they are dropped: the new one
    stands for them).  Intervals end in the order they are booked, so
    one that started after ``start`` lies inside."""
    done = _tls.intervals
    if done is None:
        done = _tls.intervals = []
    inner = 0
    while done and done[-1][0] >= start:
        inner += done.pop()[1]
    done.append((start, dur))
    if len(done) > _MAX_INTERVALS:
        # never inside a statement (it starts with an empty list and one
        # program's compile is hundreds of events): a thread that only
        # ever compiles gives up its oldest siblings
        del done[:_MAX_INTERVALS // 2]
    return inner


def _book_compile(stage: tuple, start: float, end: float):
    """One compile event's SELF time goes to the thread's statement (the
    phase of its stage) and is charged to the open span as child time.
    Events nest (a jitted function traced inside another's trace; the
    cache retrieval inside the backend compile) and the inner one ends
    first, so an event gives up what the events it encloses own."""
    dur = int((end - start) * 1e9)
    own = max(dur - _enclosed_ns(start, dur), 0)
    qmetrics.inc("jax.compile_ns", own, stage=stage[0])
    qmetrics.inc("jax.compile_events")
    if not _tls.bracketed:
        book_owned(stage[1], own)


def _on_jax_span(name: str, start: float, end: float, **_kw):
    """jax.monitoring time-span listener (one for the process): trace,
    lowering and backend compile, with their own wall-clock stamps."""
    stage = _JAX_STAGES.get(name)
    if stage is not None:
        _book_compile(stage, start, end)


def _on_jax_duration(name: str, secs: float, **_kw):
    """The persistent cache's retrieval reports a duration only, as it
    ends."""
    if name == _CACHE_RETRIEVAL:
        now = time.time()
        _book_compile(("cache_lookup", "cache_lookup_s"), now - secs, now)


#: (pause ns, generation) of collections not yet in the registry: the
#: callback may run inside ANY allocation (the registry's own included),
#: so it touches no lock and no dict; begin_statement() flushes
_gc_pending: collections.deque = collections.deque()


def _on_gc(phase: str, info: dict):
    """``gc.callbacks``: runs in the thread whose allocation triggered
    the collection, so the pause is that thread's statement's."""
    if phase == "start":
        ann = annotation("gc")
        ann.__enter__()
        _tls.gc = (ann, time.perf_counter_ns(), time.time())
        return
    st = _tls.gc
    if st is None:
        return
    _tls.gc = None
    dur = time.perf_counter_ns() - st[1]
    st[0].__exit__(None, None, None)
    # a compile event this pause struck inside gives the time up too
    _enclosed_ns(st[2], dur)
    book_owned("gc_s", dur)
    _gc_pending.append((dur, info.get("generation", 0)))


def begin_statement():
    """Statement start on this thread: pending collector pauses ->
    ``runtime.gc_pause_ns`` / ``runtime.gc_collections{gen}``, and an
    empty list of booked intervals (none of an earlier statement can lie
    inside an event of this one)."""
    done = _tls.intervals
    if done:
        done.clear()
    while _gc_pending:
        try:
            dur, gen = _gc_pending.popleft()
        except IndexError:
            return
        qmetrics.inc("runtime.gc_pause_ns", dur)
        qmetrics.inc("runtime.gc_collections", gen=gen)


_hooks_installed = False


def install_runtime_hooks():
    """Idempotent, process-wide (listeners cannot be removed)."""
    global _hooks_installed
    if _hooks_installed:
        return
    _hooks_installed = True
    import gc

    import jax

    jax.monitoring.register_event_time_span_listener(_on_jax_span)
    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    gc.callbacks.append(_on_gc)


def absorb(ctx: TraceCtx, wire_spans: list) -> None:
    """Merge spans shipped back in an rpc reply into this context."""
    for d in wire_spans:
        try:
            ctx.add(Span.from_wire(d))
        except (KeyError, TypeError, ValueError):
            continue  # a malformed remote span must not fail the query


# ---------------------------------------------------------------------------
# statement lifecycle (the session's entry points)
# ---------------------------------------------------------------------------


def start_trace(db) -> TraceCtx | None:
    """-> a fresh per-statement context, or None when tracing is off."""
    cfg = db.config
    if not bool(cfg["enable_query_trace"]):
        return None
    rate = float(cfg["trace_sample_rate"])
    slow = float(cfg["trace_slow_threshold_s"])
    sampled = rate >= 1.0 or random.random() < rate
    return TraceCtx(f"{random.getrandbits(64):016x}",
                    node=db.node_id,
                    sampled=sampled, slow_s=slow)


def finish_trace(db, ctx: TraceCtx, elapsed_s: float,
                 error: str = "") -> bool:
    """Retention decision at statement end: sampled-in traces keep, and a
    slow or failed statement keeps its tree regardless of the sample
    draw (the 'slow queries always traced' contract).  A slow statement
    (over ``trace_slow_threshold_s``, or marked far over its
    plan_history baseline) goes to the registry's slow ring, which fast
    statements cannot evict.  -> kept?"""
    slow = elapsed_s >= ctx.slow_s or ctx.slow
    keep = ctx.sampled or slow or bool(error)
    reg = db.trace_registry
    spans, ctx.spans = ctx.spans, []
    # the context lets go of its spans either way (they point back at
    # it: no cycle is left for the collector), and the registry packs
    # the tree, so every span object of the statement dies here
    if keep and spans:
        reg.add(spans, slow=slow)
    else:
        reg.note_dropped()
        keep = False
    return keep
