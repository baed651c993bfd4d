"""The streamed path's spans in a capture: ``ob:granule.fetch`` and
``ob:granule.upload`` (the producer thread: host decode of one granule, its
copy to the device), ``ob:granule.program`` (dispatch and wait of the chunk
program) and ``ob:granule.merge`` (partial states to the result).

``program_spans`` reads the thread that carries ``bench:execute``; the
producer's spans are on a thread of their own, so this reader takes the
events of EVERY host thread that lie inside a statement's
``bench:execute`` span, whole durations (a span's children are part of
what a statement pays for it).  Where the program writes no such span (a
parent commit, or a cell whose statements do not stream) every reader
returns ``None``.
"""

from __future__ import annotations

import os

from . import program_spans, stats, tracing, xplane

PREFIX = program_spans.OB + "granule."
NAMES = ("fetch", "upload", "program", "merge")


def reduce_profile(profile) -> list[dict]:
    """One capture -> a dict per ``bench:execute`` span, in order: seconds
    and count of each granule span inside it, and the device busy seconds
    under its ``granule.program`` spans."""
    execs, events = [], []
    for line in program_spans._host_lines(profile):
        for e in line.events:
            span = (e.start_ns, e.start_ns + e.duration_ns)
            if e.name.startswith(program_spans.EXECUTE):
                execs.append(span)
            elif e.name.startswith(PREFIX):
                events.append((e.name[len(PREFIX):],) + span)
    busy = [xplane.union((a, b) for _n, a, b in evs)
            for evs, _async in xplane._device_ops(profile).values()]
    out = []
    for s0, s1 in sorted(execs):
        mine = [(n, a, b) for n, a, b in events if a >= s0 and b <= s1]
        st = {"seconds": {}, "count": {}, "program_busy_s": 0.0}
        for name in NAMES:
            spans = [(a, b) for n, a, b in mine if n == name]
            st["seconds"][name] = sum(b - a for a, b in spans) * 1e-9
            st["count"][name] = len(spans)
        programs = [(a, b) for n, a, b in mine if n == "program"]
        st["program_busy_s"] = max(
            (sum(xplane.total(xplane.clip(dev, a, b)) for a, b in programs)
             for dev in busy), default=0.0) * 1e-9
        out.append(st)
    return out


_by_cell: dict = {}


def load(record) -> dict | None:
    """template -> [per-statement dict] for the captures of ``record``;
    ``None`` unless some traced statement holds a granule span."""
    cell = record["cell"]["name"]
    if cell not in _by_cell:
        got = {}
        for cap in record.get("captures") or []:
            files = tracing.xplane_files(
                program_spans.capture_dir(cell, cap["template"]))
            if len(files) != 1 or os.path.getsize(files[0]) > \
                    program_spans.MAX_XPLANE_BYTES:
                continue
            got[cap["template"]] = reduce_profile(xplane.load(files[0]))
        _by_cell[cell] = got
    got = _by_cell[cell]
    if not any(st["count"][n] for sts in got.values() for st in sts
               for n in NAMES):
        return None
    return got


def per_statement_ms(record, name: str) -> float | None:
    """Median over the traced statements of the summed durations of the
    statement's ``granule.<name>`` spans, in ms."""
    got = load(record)
    if got is None:
        return None
    xs = [st["seconds"][name] * 1e3 for sts in got.values() for st in sts]
    return stats.median(xs) if xs and max(xs) > 0 else None


def window_executions(record) -> dict:
    """template -> the window's answered statements of it."""
    sent: dict[str, int] = {}
    for rec in record["window"]:
        if rec["error"] is None:
            sent[rec["template"]] = sent.get(rec["template"], 0) + 1
    return sent
