"""session: of the window's statements the work-area budget priced
(``sql.work_area_decisions{kind=resident|spill}``), the share it kept on
the device, off the disk spill tier.  100 in a deployment that sizes
``ob_sql_work_area_percentage`` as upstream's TPC-H guide does; it falls
when a statement's inputs are priced over the budget.  ``None`` when the
window priced nothing, or the program has no such counter."""

RESIDENT = "sql.work_area_decisions{kind=resident}"
SPILL = "sql.work_area_decisions{kind=spill}"


def compute(record):
    before, after = record["counters_before"], record["counters_after"]
    resident, spill = (after.get(k, 0.0) - before.get(k, 0.0)
                       for k in (RESIDENT, SPILL))
    if resident + spill <= 0:
        return None
    return 100.0 * resident / (resident + spill)
