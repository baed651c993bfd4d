"""operators: of the inputs of the joins and index probes executed in the
window (``plan.join_inputs{kind=compacted|whole}``: the planner decides at
bind time, from an input's estimate and the lanes of the scan under it),
the share that reached its join densified to its estimate's bucket and not
on the lanes of what lies under it.  ``None`` when the window ran no join,
or the program has no such counter."""

COMPACTED = "plan.join_inputs{kind=compacted}"
WHOLE = "plan.join_inputs{kind=whole}"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    compacted = after.get(COMPACTED, 0.0) - before.get(COMPACTED, 0.0)
    whole = after.get(WHOLE, 0.0) - before.get(WHOLE, 0.0)
    if compacted + whole <= 0:
        return None
    return 100.0 * compacted / (compacted + whole)
