"""PX: the least time the window's exchanged rows need on the interconnect
(``px.exchange_bytes`` of every kind: live rows received x their row width;
the part that crossed chips, a chip's share of it, over the published ICI
bandwidth of one chip: ``harness/exchange_bytes_model``) divided by the
time the collectives took: each template's window executions times the
collective time of one traced execution on the device that spent most in
collectives.  Live rows only: padding on the wire lowers it.

The collectives' time is read here from the capture's own ``.xplane.pb``,
by OPCODE: the reduction's ``collective_s`` matches a collective by the
start of its instruction's NAME, and JAX names an instruction after its
primitive (``%all_to_all.42 = ... all-to-all(...)``: underscores), so on
the chip it holds the ``all-reduce``s the compiler named and none of the
``all-to-all``s that carry the rows (PR 42's first check read 127 % over
that time).  Every collective counts, also those whose bytes are not in
``px.exchange_bytes`` (the overflow totals' ``all-reduce``): they can only
lower the share.  ``None`` where nothing was exchanged, a capture is not
there to read, or the program counts no exchange bytes."""

from benchmark.harness import (exchange_bytes_model, peaks, program_spans,
                               tracing, xplane)

SERIES = "px.exchange_bytes"
COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "collective-broadcast",
               "ragged-all-to-all")


def is_collective(label: str) -> bool:
    """``label`` is ``xplane.op_label``'s: ``<name> <opcode>[/kind] <shapes>``
    of an HLO line (the chip), or a bare op name (the CPU's host plane).
    ``-start`` / ``-done`` halves of an asynchronous collective count, and
    so does an op that carries a collective's name without its opcode (the
    ``reshape`` that ``all_to_all``'s lowering leaves beside it): more time,
    never less."""
    name, _, rest = label.partition(" ")
    opcode = rest.partition(" ")[0].partition("/")[0]
    return opcode.startswith(COLLECTIVES) or \
        name.lower().replace("_", "-").startswith(COLLECTIVES)


def collective_seconds(profile) -> float:
    """Seconds in which a collective ran (synchronous ops and the spans of
    asynchronous ones, their union), on the device that spent most so."""
    worst = 0.0
    for evs, async_evs in xplane._device_ops(profile).values():
        worst = max(worst, xplane.total(xplane.union(
            (a, b) for n, a, b in evs + async_evs if is_collective(n))))
    return worst * 1e-9


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    received = sum(v - before.get(k, 0.0) for k, v in after.items()
                   if k.startswith(SERIES))
    if received <= 0:
        return None
    sent = {}
    for rec in record["window"]:
        if rec["error"] is None:
            sent[rec["template"]] = sent.get(rec["template"], 0) + 1
    took = 0.0
    for cap in record["captures"]:
        if not cap["reduced"]:  # too large to load, or no device op in it
            return None
        files = tracing.xplane_files(program_spans.capture_dir(
            record["cell"]["name"], cap["template"]))
        if len(files) != 1:
            return None
        took += sent.get(cap["template"], 0) \
            * collective_seconds(xplane.load(files[0])) / cap["executions"]
    if took <= 0:
        return None
    peak = peaks.peaks_for(record["device"]["kind"])["ici_bits_per_s"]
    least = exchange_bytes_model.least_seconds(
        received, record["device"]["count"], peak)
    return 100.0 * least / took
