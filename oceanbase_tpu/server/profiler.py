"""Deep device profiling: PROFILE <statement> -> per-kernel rows.

Reference analog: the SQL-plan-monitor's per-operator timing made
kernel-real — ``PROFILE <query>`` wraps one statement in a
``jax.profiler`` device trace, parses the captured trace into
per-kernel rows (name, occurrences, total/avg time, share of device
time), and stores them keyed by the statement's trace_id so
``gv$device_profile`` joins against gv$sql_audit / gv$trace.  ``SHOW
PROFILE`` renders the session's most recent capture.

The capture degrades gracefully everywhere the backend can't profile:
the statement always executes; a profiler failure just yields a note
instead of rows.  The parser reads the capture's ``.xplane.pb`` through
``jax.profiler.ProfileData`` (nothing but JAX) into

- ``kernel`` — the executed XLA ops, named as the device plane names
  them (the rows the roofline plane cares about);
- ``host``   — the program's own spans (``ob:<name>``): the statement's
  phases on the same timeline.

Only one trace can be active per process (a jax.profiler constraint):
concurrent PROFILEs serialize on a non-blocking lock — the loser runs
unprofiled with a note, it never deadlocks a session.
"""

from __future__ import annotations

import collections
import glob
import os
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass, field

_PROFILE_LOCK = threading.Lock()

MAX_ROWS_PER_PROFILE = 256


@dataclass
class DeviceProfile:
    """One PROFILE capture (joined to the statement by trace_id)."""

    trace_id: str
    sql: str
    backend: str
    ts: float                  # wall clock (record timestamp)
    rows: list = field(default_factory=list)
    note: str = ""


class DeviceProfileStore:
    """Bounded ring of PROFILE captures (the gv$device_profile store)."""

    def __init__(self, capacity: int = 64):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()

    def record(self, prof: DeviceProfile):
        with self._lock:
            self._ring.append(prof)

    def recent(self, n: int | None = None) -> list:
        with self._lock:
            items = list(self._ring)
        return items if n is None else items[-n:]

    def get(self, trace_id: str) -> DeviceProfile | None:
        with self._lock:
            for p in reversed(self._ring):
                if p.trace_id == trace_id:
                    return p
        return None


# ---------------------------------------------------------------------------
# capture
# ---------------------------------------------------------------------------


def profile_statement(run):
    """Execute ``run()`` under a device trace.  -> (result, rows, note).

    The statement's own exception always propagates; profiler failures
    never do.  When the profiler cannot even start (another trace
    active, backend without one), the statement runs unprofiled."""
    if not _PROFILE_LOCK.acquire(blocking=False):
        return run(), [], "profiler busy (another PROFILE in flight)"
    try:
        tmpdir = tempfile.mkdtemp(prefix="obtpu_profile_")
        try:
            try:
                import jax

                # the statement's phases come from the program's own
                # spans, not from a frame per Python call
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                cm = jax.profiler.trace(tmpdir, profiler_options=options)
                cm.__enter__()
            except Exception as e:  # noqa: BLE001 — no profiler on
                # this backend: the statement still runs
                return run(), [], (f"profiler unavailable: "
                                   f"{type(e).__name__}: {e}"[:200])
            note = ""
            try:
                out = run()
            finally:
                try:
                    cm.__exit__(None, None, None)
                except Exception as e:  # noqa: BLE001
                    note = (f"profiler stop failed: "
                            f"{type(e).__name__}: {e}"[:200])
            rows = [] if note else parse_trace_dir(tmpdir)
            if not rows and not note:
                note = "profiler produced no device events"
            return out, rows, note
        finally:
            shutil.rmtree(tmpdir, ignore_errors=True)
    finally:
        _PROFILE_LOCK.release()


# ---------------------------------------------------------------------------
# parse (the capture's .xplane.pb, through jax.profiler.ProfileData)
# ---------------------------------------------------------------------------

#: lines of a device plane that hold one event per executed HLO op
_OP_LINES = ("XLA Ops",)
_MAX_NAME = 256


def parse_trace_dir(tmpdir: str) -> list:
    """Newest ``*.xplane.pb`` under a jax.profiler log dir -> aggregated
    rows (sorted by total time, bounded):

    - ``kernel`` — one per executed XLA op, named as the device plane
      names it: the ``XLA Ops`` line of each ``/device:*`` plane, or, on
      a backend without device planes (the CPU), the host events that
      carry an ``hlo_op`` stat;
    - ``host`` — the program's own spans (``ob:<name>``, server/trace.py)
      inside the capture: the statement's phases on the same clock.
    """
    files = sorted(glob.glob(os.path.join(
        tmpdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return []
    try:
        from jax.profiler import ProfileData

        profile = ProfileData.from_file(files[-1])
    except Exception:  # noqa: BLE001 — an unreadable capture is no rows
        return []
    from oceanbase_tpu.server.trace import ANNOTATION_PREFIX

    agg: dict[tuple, list] = {}

    def add(plane: str, name: str, kind: str, dur_ns: float):
        cur = agg.setdefault((plane, name[:_MAX_NAME], kind), [0, 0.0])
        cur[0] += 1
        cur[1] += dur_ns * 1e-9

    device_planes = [p for p in profile.planes
                     if p.name.startswith("/device:")]
    for plane in device_planes:
        for line in plane.lines:
            if line.name in _OP_LINES:
                for e in line.events:
                    add(plane.name, e.name, "kernel", e.duration_ns)
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ANNOTATION_PREFIX):
                    add(plane.name, e.name, "host", e.duration_ns)
                elif not device_planes and e.duration_ns > 0 and \
                        any(k == "hlo_op" for k, _ in e.stats):
                    add(plane.name, e.name, "kernel", e.duration_ns)
    kernel_total = sum(v[1] for (_pl, _n, kind), v in agg.items()
                       if kind == "kernel")
    rows = []
    for (plane, name, kind), (occ, total) in agg.items():
        rows.append({
            "device": plane, "kernel": name, "kind": kind,
            "occurrences": int(occ), "total_s": total,
            "avg_s": total / occ if occ else 0.0,
            "pct": (100.0 * total / kernel_total
                    if kind == "kernel" and kernel_total > 0 else 0.0)})
    rows.sort(key=lambda r: -r["total_s"])
    return rows[:MAX_ROWS_PER_PROFILE]


def make_profile(trace_id: str, sql: str, rows: list,
                 note: str = "") -> DeviceProfile:
    from oceanbase_tpu.server.backend_info import resolve_backend

    return DeviceProfile(trace_id=trace_id, sql=sql[:200],
                         backend=resolve_backend()["platform"],
                         ts=time.time(), rows=rows, note=note)
