"""storage to device: the least time the traced deltas' own bytes could
take (rows written x the lane width of all columns with their validity and
the row mask, plus lanes cleared x the mask's width, from the loaded
relations' layout and the dataset's row sets: ``harness/delta_bytes_model``)
over the published HBM bandwidth, divided by the device busy time under
``ob:storage.delta_apply`` in the captures.  Far under 1 %: an apply moves
kilobytes through programs that copy whole columns, so it is bound by
latency and by the copy, not by the delta's bytes.  ``None`` where no
traced read applied a delta."""

from benchmark.harness import (delta_bytes_model, peaks, program_spans, spec,
                               tracing, xplane)

SPAN = program_spans.OB + "storage.delta_apply"


def busy_under(profile, name: str) -> tuple[float, int]:
    """-> (device busy seconds inside the host events called ``name``, how
    many there are); the busiest device where there are several."""
    spans = [(e.start_ns, e.start_ns + e.duration_ns)
             for line in program_spans._host_lines(profile)
             for e in line.events if e.name == name]
    busy = 0.0
    for evs, _async in xplane._device_ops(profile).values():
        ops = xplane.union((a, b) for _n, a, b in evs)
        busy = max(busy, sum(xplane.total(xplane.clip(ops, a, b))
                             for a, b in spans))
    return busy * 1e-9, len(spans)


def compute(record):
    peak = peaks.peaks_for(record["device"]["kind"])["hbm_bytes_per_s"]
    dataset = spec.load_module("datasets",
                               record["config"]["dataset"]["generator"])
    sent = record["window"] + record["traced"]
    least = busy = 0.0
    for cap in record["captures"]:
        reads = record["statements"][cap["template"]].get("reads")
        if not reads:
            continue
        files = tracing.xplane_files(program_spans.capture_dir(
            record["cell"]["name"], cap["template"]))
        if len(files) != 1:
            return None
        sec, n = busy_under(xplane.load(files[0]), SPAN)
        if n == 0:
            continue
        busy += sec
        # the capture's first read brings its tables up by everything
        # committed since the read before it; its later reads find them
        # current
        first = next(i for i, rec in enumerate(sent)
                     if rec["phase"] == "trace"
                     and rec["template"] == cap["template"])
        writes = delta_bytes_model.committed_between(sent, first)
        for table in reads:
            rows, cleared = delta_bytes_model.table_delta(
                writes, record["statements"], dataset, record["scale"],
                record["seed"], table)
            least += delta_bytes_model.delta_bytes(
                record["layouts"][table], rows, cleared) / peak
    return 100.0 * least / busy if busy > 0 else None
