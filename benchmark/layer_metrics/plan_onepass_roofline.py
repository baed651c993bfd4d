"""kernels (the plan's XLA program): the least time one pass over the
columns a statement reads could take (bytes at bucket capacity from the
loaded relations' dtypes, over the published HBM bandwidth), divided by the
statement's device busy time in the trace; both summed over one pass of
the mix.  Bound by bytes, not operations.  A join needs more than one pass,
so this is a ceiling on the true share; it is honest for a single scan."""

from benchmark.harness import bytes_model, peaks


def compute(record):
    peak = peaks.peaks_for(record["device"]["kind"])["hbm_bytes_per_s"]
    least = busy = 0.0
    for cap in record["captures"]:
        red = cap["reduced"]
        if not red:
            return None
        spans = [s for s in red["spans"] if s["kind"] == "execute"]
        if not spans:
            return None
        reads = record["statements"][cap["template"]]["reads"]
        least += bytes_model.least_seconds(reads, record["layouts"], peak)
        busy += sum(s["busy_max_s"] for s in spans) / len(spans)
    return 100.0 * least / busy if busy > 0 else None
