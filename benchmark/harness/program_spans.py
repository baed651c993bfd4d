"""The program's own spans on the profiler's timeline.

Since PR 24 every span of the program (``oceanbase_tpu/server/trace.py``)
is also a ``jax.profiler.TraceAnnotation`` named ``ob:<name>``: an event on
the host plane of the same ``.xplane.pb`` as the device ops, nested inside
the benchmark's ``bench:execute:<template>``.  This file reads them:

- per span name, the SELF time per statement (its duration minus what the
  ``ob:`` events nested in it cover),
- per statement, what no ``ob:`` LEAF span covers (a leaf has no ``ob:``
  event inside it but a collector pause, ``ob:gc``: a parent's own time
  between its children is glue, and glue has no name),
- the device-idle time under each leaf span, and the idle time under none.

The captures are read from where the runner left them
(``spec.SCRATCH_DIR/trace/<cell>/<template>/``); they are still there when
the metrics are computed.  Where the program writes no ``ob:`` event (a
parent commit of PR 24) every reader here returns ``None``.

    python3 -m benchmark.harness.program_spans <cell>

prints the tables "host phase by span" and "idle gap by program span" of
the cell's last traced run.
"""

from __future__ import annotations

import os
import sys

from . import spec, stats, tracing, xplane

OB = "ob:"
PAUSE = OB + "gc"
EXECUTE = tracing.SPAN_PREFIX + "execute:"
UNOWNED = "(no ob: leaf span)"
#: a capture larger than this is not read (the runner's own limit)
MAX_XPLANE_BYTES = 400 << 20

def nest(events):
    """[(name, start, end)] of ONE thread -> [(name, start, end, self,
    is_leaf)]: an event nested in another takes its time from it, and an
    event with none nested in it is a leaf."""
    out = []
    stack: list[list] = []  # [name, start, end, covered, has_child]

    def close(ev):
        out.append((ev[0], ev[1], ev[2],
                    max(0.0, (ev[2] - ev[1]) - ev[3]), not ev[4]))

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and a >= stack[-1][2]:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(b, stack[-1][2]) - a
            stack[-1][4] = True
        stack.append([name, a, b, 0.0, False])
    while stack:
        close(stack.pop())
    return out


def _host_lines(profile):
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                yield line


def reduce_profile(profile) -> dict | None:
    """One capture -> per-statement span arithmetic; ``None`` where it
    holds no ``bench:execute`` span or no ``ob:`` event at all."""
    statements = []   # one dict per bench:execute span
    ob_leaves = []    # (name, start, end) of every ob: leaf in a statement
    for line in _host_lines(profile):
        evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
               for e in line.events
               if e.name.startswith(OB) or e.name.startswith(EXECUTE)]
        execs = [e for e in evs if e[0].startswith(EXECUTE)]
        if not execs:
            continue
        obs = [e for e in evs if e[0].startswith(OB)]
        for _name, s0, s1 in sorted(execs, key=lambda e: e[1]):
            mine = [e for e in obs if e[1] >= s0 and e[2] <= s1]
            self_ns: dict[str, float] = {}
            for name, _a, _b, own, _leaf in nest(mine):
                self_ns[name[len(OB):]] = \
                    self_ns.get(name[len(OB):], 0.0) + own
            # a collector pause strikes inside whatever span is open: it
            # is a leaf of its own, and the span around it stays one,
            # less the pause
            pauses = sorted((a, b) for n, a, b in mine if n == PAUSE)
            leaves = [("gc", a, b) for a, b in pauses]
            for n, a, b, _own, leaf in nest(
                    [e for e in mine if e[0] != PAUSE]):
                if leaf:
                    leaves += [(n[len(OB):], x, y) for x, y in
                               xplane.complement(xplane.clip(pauses, a, b),
                                                 a, b)]
            covered = xplane.union((a, b) for _n, a, b in leaves)
            holes = {}
            edges = sorted(leaves, key=lambda e: e[1])
            for a, b in xplane.complement(covered, s0, s1):
                before = max((e for e in edges if e[2] <= a),
                             key=lambda e: e[2], default=None)
                after = min((e for e in edges if e[1] >= b),
                            key=lambda e: e[1], default=None)
                key = (f"{before[0] if before else 'start'} -> "
                       f"{after[0] if after else 'end'}")
                holes[key] = holes.get(key, 0.0) + (b - a)
            statements.append({
                "start_ns": s0, "end_ns": s1, "self_ns": self_ns,
                "unowned_ns": (s1 - s0) - xplane.total(covered),
                "holes_ns": holes})
            ob_leaves += leaves
    if not statements or not any(st["self_ns"] for st in statements):
        return None
    # device idle time, by the leaf span it lies under
    planes = xplane._device_ops(profile)
    spans = xplane._spans(profile, tracing.SPAN_PREFIX)
    lo, hi = spans[0][2], max(s[3] for s in spans)
    busy = xplane.union(
        iv for evs, _async in planes.values()
        for iv in xplane.clip([(a, b) for _n, a, b in evs], lo, hi))
    idle = xplane.complement(busy, lo, hi)
    idle_ns: dict[str, float] = {}
    under_leaf = 0.0
    for name, a, b in ob_leaves:
        sec = xplane.total(xplane.clip(idle, a, b))
        if sec > 0:
            idle_ns[name] = idle_ns.get(name, 0.0) + sec
            under_leaf += sec
    idle_total = xplane.total(idle)
    idle_ns[UNOWNED] = max(idle_total - under_leaf, 0.0)
    return {"statements": statements, "idle_ns": idle_ns,
            "idle_total_ns": idle_total, "device_ops": bool(busy)}


def capture_dir(cell: str, template: str) -> str:
    return os.path.join(spec.SCRATCH_DIR, "trace", cell, template)


def load_capture(cell: str, template: str) -> dict | None:
    """The reduction of one template's capture."""
    files = tracing.xplane_files(capture_dir(cell, template))
    if len(files) != 1 or os.path.getsize(files[0]) > MAX_XPLANE_BYTES:
        return None
    return reduce_profile(xplane.load(files[0]))


def load(record) -> dict | None:
    """template -> reduction, for the captures of ``record``; ``None``
    unless every template's capture holds the program's spans."""
    out = {}
    for cap in record["captures"]:
        red = _captures(record["cell"]["name"]).get(cap["template"])
        if red is None:
            return None
        out[cap["template"]] = red
    return out or None


_by_cell: dict = {}


def _captures(cell: str) -> dict:
    """Every template's reduction of a cell, read once per process."""
    if cell not in _by_cell:
        base = os.path.join(spec.SCRATCH_DIR, "trace", cell)
        names = sorted(os.listdir(base)) if os.path.isdir(base) else []
        _by_cell[cell] = {t: load_capture(cell, t) for t in names}
    return _by_cell[cell]


# -- what the layer metrics read -------------------------------------------

def self_ms(record, *names: str) -> float | None:
    """Median over ALL traced statements of the summed self time of the
    named spans, in ms; ``None`` where the captures hold none of them."""
    reds = load(record)
    if reds is None:
        return None
    xs = [sum(st["self_ns"].get(n, 0.0) for n in names) * 1e-6
          for red in reds.values() for st in red["statements"]]
    return stats.median(xs) if xs and max(xs) > 0 else None


def unowned_ms(record) -> float | None:
    reds = load(record)
    if reds is None:
        return None
    return stats.median([st["unowned_ns"] * 1e-6
                         for red in reds.values()
                         for st in red["statements"]])


def idle_under_no_span_pct(record) -> float | None:
    reds = load(record)
    if reds is None or not all(r["device_ops"] for r in reds.values()):
        return None
    idle = sum(r["idle_total_ns"] for r in reds.values())
    if idle <= 0:
        return None
    return 100.0 * sum(r["idle_ns"][UNOWNED] for r in reds.values()) / idle


# -- the tables of PERF.md section 5 ---------------------------------------

def tables(cell: str) -> str:
    lines = []
    for template, red in _captures(cell).items():
        if red is None:
            lines.append(f"{template}: no ob: spans in the capture")
            continue
        sts = red["statements"]
        n = len(sts)
        execute = stats.median([(s["end_ns"] - s["start_ns"]) * 1e-6
                                for s in sts])
        lines.append(f"## {cell} / {template}: {n} traced statement(s), "
                     f"median bench:execute {execute:.4f} ms")
        lines.append("| span | median self ms | mean self ms | share of "
                     "execute % | device idle under it ms/stmt |")
        lines.append("| --- | --- | --- | --- | --- |")
        names = sorted({k for s in sts for k in s["self_ns"]},
                       key=lambda k: -sum(s["self_ns"].get(k, 0.0)
                                          for s in sts))
        total_exec = sum(s["end_ns"] - s["start_ns"] for s in sts)
        for k in names:
            xs = [s["self_ns"].get(k, 0.0) * 1e-6 for s in sts]
            lines.append(
                f"| {k} | {stats.median(xs):.4f} | {sum(xs) / n:.4f} | "
                f"{100.0 * sum(xs) * 1e6 / total_exec:.2f} | "
                f"{red['idle_ns'].get(k, 0.0) * 1e-6 / n:.4f} |")
        un = [s["unowned_ns"] * 1e-6 for s in sts]
        lines.append(
            f"| {UNOWNED} | {stats.median(un):.4f} | {sum(un) / n:.4f} | "
            f"{100.0 * sum(un) * 1e6 / total_exec:.2f} | "
            f"{red['idle_ns'][UNOWNED] * 1e-6 / n:.4f} |")
        holes: dict[str, float] = {}
        for s in sts:
            for k, v in s["holes_ns"].items():
                holes[k] = holes.get(k, 0.0) + v
        top = sorted(holes.items(), key=lambda kv: -kv[1])[:8]
        lines.append("unowned, by the leaf spans around it (ms/stmt): "
                     + "; ".join(f"{k}: {v * 1e-6 / n:.4f}"
                                 for k, v in top))
        lines.append(f"device idle in the capture: "
                     f"{red['idle_total_ns'] * 1e-6 / n:.4f} ms/stmt, "
                     f"under no leaf span "
                     f"{red['idle_ns'][UNOWNED] * 1e-6 / n:.4f}")
        lines.append("")
    return "\n".join(lines) if lines else f"no capture of {cell}"


if __name__ == "__main__":
    print(tables(sys.argv[1]))
