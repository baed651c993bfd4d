"""kernels (the chunk program): the least time the traced statements' chunk
programs could take to read their inputs (a granule of the streamed table
and each resident table the statement joins, the columns of its ``reads``:
``harness/granule_bytes_model``, over the published HBM bandwidth) divided
by the device busy time under ``ob:granule.program`` in the captures.
Bound by bytes; a probe needs more than one pass, so this is a ceiling on
the true share.  ``None`` where no traced statement streamed, or the
program's cache names no granule shape."""

from benchmark.harness import granule_bytes_model, granule_spans, peaks


def compute(record):
    got = granule_spans.load(record)
    lanes = granule_bytes_model.granule_lanes(
        v["plan_text"] for v in record["plan_traces_after"].values())
    if got is None or lanes is None:
        return None
    peak = peaks.peaks_for(record["device"]["kind"])["hbm_bytes_per_s"]
    least = busy = 0.0
    for template, sts in got.items():
        reads = record["statements"][template]["reads"]
        for st in sts:
            least += granule_bytes_model.least_seconds(
                reads, record["layouts"], lanes, st["count"]["program"], peak)
            busy += st["program_busy_s"]
    return 100.0 * least / busy if busy > 0 else None
