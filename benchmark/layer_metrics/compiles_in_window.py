"""plan cache + compile: what compiled inside the measured window.  The
larger of the change of the summed ``gv$plan_cache.xla_trace_count`` over
the window (plans that read no virtual table; PX plans are not in that
table) and JAX's own count of trace, lowering and backend-compile events
in the window.  Expected 0."""


def compute(record):
    def total(snapshot):
        return sum(v["xla_trace_count"] for v in snapshot.values())

    return max(total(record["plan_traces_after"])
               - total(record["plan_traces_before"]),
               record["compile_events_in_window"])
