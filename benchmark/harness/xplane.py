"""From the profiler's ``.xplane.pb`` to numbers: the reduction the traced
run's metrics read.  Needs nothing but JAX (``jax.profiler.ProfileData``).

What it computes, for one capture:

- the traced window: from the start of the benchmark's first span to the
  end of its last (``bench:<kind>:<label>`` annotations on a host thread),
- per device: the union of the intervals in which an XLA op ran (busy
  time), and each op's SELF time (its duration minus what ops nested in
  it cover, so that a ``while`` does not hide the sort inside it),
- the idle gaps (no device busy), each attributed to the benchmark span
  that covers it, or to "between statements",
- per span: the device busy time inside it.

Times are seconds; positions are relative to the window's start.
"""

from __future__ import annotations

import re

#: lines of a device plane that hold one event per executed HLO op
OP_LINES = ("XLA Ops",)
#: lines with the spans of asynchronous ops (start to done): copies and,
#: where the compiler made them asynchronous, collectives.  They overlap
#: the ops above and count in no busy time, only in the collectives' time
ASYNC_LINES = ("Async XLA Ops",)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
COLLECTIVE = re.compile(
    r"^(all-to-all|all-gather|all-reduce|reduce-scatter|collective-permute|"
    r"collective-broadcast|ragged-all-to-all)", re.I)
BETWEEN = "between statements"


_HLO = re.compile(r"^%(?P<name>\S+) = (?P<shape>.+?) (?P<opcode>[a-z][a-z0-9\-]*)"
                  r"\((?P<rest>.*)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_ARRAY = re.compile(r"\b[a-z]+[0-9]*\[[0-9,]*\]")
_ATTR = re.compile(r"\b(?:kind=(k[A-Za-z]+)|custom_call_target=\"([^\"]+)\")")


def op_label(event_name: str, limit: int = 120) -> str:
    """A device op's name as the breakdown prints it.  The TPU's trace names
    an op by its whole HLO line; what identifies it is its name, opcode,
    fusion kind or custom-call target, result shape and operand shapes."""
    m = _HLO.match(event_name)
    if not m:
        return event_name[:limit]
    attrs = "".join("/" + (a or b) for a, b in _ATTR.findall(m["rest"]))
    operands = ",".join(_ARRAY.findall(_LAYOUT.sub("", m["rest"]))[:4])
    shape = _LAYOUT.sub("", m["shape"]).replace(" ", "")
    return (f"{m['name']} {m['opcode']}{attrs} {shape}<-({operands})")[:limit]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def load_text(text: str):
    """A capture kept as a text-format ``XSpace`` (the tests' fixture)."""
    from jax.profiler import ProfileData

    return ProfileData.from_text_proto(text)


# -- interval arithmetic --------------------------------------------------

def union(intervals) -> list[tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[list[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def complement(disjoint, lo: float, hi: float):
    out, at = [], lo
    for a, b in disjoint:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(events) -> dict[str, list]:
    """name -> [self seconds, count] for events (name, start, end) of one
    line, where an event nested in another takes its time from it."""
    out: dict[str, list] = {}
    stack: list[list] = []  # [name, start, end, covered by children]

    def close(ev):
        acc = out.setdefault(ev[0], [0.0, 0])
        acc[0] += max(0.0, (ev[2] - ev[1]) - ev[3])
        acc[1] += 1

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and a >= stack[-1][2]:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(b, stack[-1][2]) - a
        stack.append([name, a, b, 0.0])
    while stack:
        close(stack.pop())
    return out


# -- reading the planes ---------------------------------------------------

def _device_ops(profile) -> dict[str, tuple[list, list]]:
    """device plane name -> (ops, async ops), each [(label, start_ns,
    end_ns)].  Where the backend has no device plane (the CPU, in a
    rehearsal), XLA's ops are the host events that carry an ``hlo_op``
    stat."""
    out: dict[str, tuple[list, list]] = {}
    for plane in profile.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        evs, async_evs = [], []
        for line in plane.lines:
            into = evs if line.name in OP_LINES else \
                async_evs if line.name in ASYNC_LINES else None
            if into is not None:
                into += [(op_label(e.name), e.start_ns,
                          e.start_ns + e.duration_ns) for e in line.events]
        out[plane.name] = (evs, async_evs)
    if out:
        return out
    evs = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns > 0 and any(k == "hlo_op" for k, _ in e.stats):
                    evs.append((e.name, e.start_ns, e.start_ns + e.duration_ns))
    return {"host": (evs, [])} if evs else {}


def _spans(profile, prefix: str) -> list[tuple[str, str, float, float]]:
    """(kind, label, start_ns, end_ns) of the benchmark's own spans."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    kind, _, label = e.name[len(prefix):].partition(":")
                    out.append((kind, label, e.start_ns,
                                e.start_ns + e.duration_ns))
    return sorted(out, key=lambda s: s[2])


def reduce_capture(profile, span_prefix: str, top: int = 40) -> dict | None:
    """One capture -> the dict the layer metrics read; ``None`` where no
    XLA op ran on a device in it."""
    planes = _device_ops(profile)
    ops = {dev: evs for dev, (evs, _async) in planes.items()}
    if not any(ops.values()):
        return None
    spans = _spans(profile, span_prefix)
    if spans:
        lo, hi = spans[0][2], max(s[3] for s in spans)
        # the device finishes what the last span enqueued inside it
        # (every statement ends in a wait), so the window is the spans'
    else:
        lo = min(e[1] for evs in ops.values() for e in evs)
        hi = max(e[2] for evs in ops.values() for e in evs)
    ns = 1e-9
    devices, busy_by_dev, all_busy = [], {}, []
    op_sums: dict[str, list] = {}
    for dev in sorted(ops):
        evs = [(n, max(a, lo), min(b, hi)) for n, a, b in ops[dev]
               if min(b, hi) > max(a, lo)]
        busy = union((a, b) for _n, a, b in evs)
        busy_by_dev[dev] = busy
        all_busy += busy
        coll = union(clip([(a, b) for n, a, b in evs + planes[dev][1]
                           if COLLECTIVE.match(n)], lo, hi))
        devices.append({"name": dev, "busy_s": total(busy) * ns,
                        "n_ops": len(evs),
                        "collective_s": total(coll) * ns})
        for name, (sec, cnt) in self_times(evs).items():
            acc = op_sums.setdefault(name, [0.0, 0])
            acc[0] += sec * ns
            acc[1] += cnt
    n_dev = len(devices)
    any_busy = union(all_busy)
    # idle gaps (no device busy), attributed to the span that covers them
    gap_sums: dict[str, float] = {}
    for a, b in complement(any_busy, lo, hi):
        covered = 0.0
        for kind, label, s0, s1 in spans:
            ov = min(b, s1) - max(a, s0)
            if ov > 0:
                key = f"{kind}:{label}"
                gap_sums[key] = gap_sums.get(key, 0.0) + ov * ns
                covered += ov
        rest = (b - a) - covered
        if rest > 0:
            gap_sums[BETWEEN] = gap_sums.get(BETWEEN, 0.0) + rest * ns
    span_rows = []
    for kind, label, s0, s1 in spans:
        inside = [total(clip(busy_by_dev[d], s0, s1)) for d in busy_by_dev]
        span_rows.append({"kind": kind, "label": label,
                          "start_s": (s0 - lo) * ns, "dur_s": (s1 - s0) * ns,
                          "busy_s": sum(inside) / n_dev * ns,
                          "busy_max_s": max(inside) * ns})
    ranked = sorted(op_sums.items(), key=lambda kv: -kv[1][0])
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(d["busy_s"] for d in devices) / n_dev,
        "any_busy_s": total(any_busy) * ns,
        "devices": devices,
        # self seconds per op name, as the mean over devices
        "ops": [[n, v[0] / n_dev, v[1]] for n, v in ranked[:top]],
        "ops_total_s": sum(v[0] for v in op_sums.values()) / n_dev,
        "gaps": sorted(([k, v] for k, v in gap_sums.items()),
                       key=lambda kv: -kv[1]),
        "spans": span_rows,
    }
