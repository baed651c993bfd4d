"""Driven by ``test_sf10_cell.py`` in a process of its own: rehearsals of a
cell that holds Q14 to its exact reference (``references/tpch_q14_exact``),
run over the same phases three times.  The first run is clean.  In the
second the program has lost ONE ``lineitem`` row of Q14's month: after the
load the lane of one such row goes dead in the resident relation, as a load
that dropped a row would leave it.  In the third the program computes Q14's
two sums in float32 (the statement reaches it with both sums' arguments
cast to FLOAT).  Prints one JSON line with the three runs' counts:
``correct`` must be held by the reference's tolerance, which one row in
750,000 and a float32's 1e-7 both have to fail.

    python3 benchmark/tests/drive_q14_faults.py <cell> <seed> <scale>
"""

import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.getcwd())

import numpy as np  # noqa: E402

from benchmark.harness import runner  # noqa: E402

PROMO_SUM = ("sum(case when p_type like 'PROMO%' then l_extendedprice * "
             "(1 - l_discount) else 0 end)")
TOTAL_SUM = "/ sum(l_extendedprice * (1 - l_discount))"


def _drop_one_row_of_the_month(run, template: str) -> int:
    """One live lane of ``lineitem`` whose ship date lies in the month the
    template asks for goes dead -> the lane."""
    from oceanbase_tpu.vector import Relation

    day = np.datetime64(run.items[template][0].params["DATE"], "D")
    d0 = int((day - np.datetime64("1970-01-01", "D")).astype(np.int64))
    d1 = int((day.astype("datetime64[M]") + np.timedelta64(1, "M")
              - np.datetime64("1970-01-01", "D")).astype(np.int64))
    catalog = run.system.session.catalog
    rel = catalog.table_data("lineitem")
    ship = np.asarray(rel.columns["l_shipdate"].data)
    lane = int(np.flatnonzero(np.asarray(rel.mask) & (ship >= d0)
                              & (ship < d1))[0])
    copy = catalog._cache.get("lineitem")
    copy.rel = Relation(rel.columns, rel.mask.at[lane].set(False))
    return lane


def _sums_in_float32(run, template: str):
    """The template's statements reach the program with both sums taken
    over FLOAT (float32) arguments."""
    real = run.system.execute
    mine = {it.sql for it in run.items[template]}

    def execute(sql):
        if sql in mine:
            assert PROMO_SUM in sql and TOTAL_SUM in sql
            sql = sql.replace(
                PROMO_SUM, "sum(cast(" + PROMO_SUM[4:-1] + " as float))"
            ).replace(TOTAL_SUM, "/ sum(cast(l_extendedprice * "
                      "(1 - l_discount) as float))")
        return real(sql)

    run.system.execute = execute


def one_run(cell: str, seed: str, scale: str, fault: str | None) -> dict:
    run = runner.Run(runner.parse_args([
        "--workload", cell, "--seed", seed, "--seconds", "2", "--trace",
        "0", "--rehearse", scale]), time.monotonic())
    template = next(t for t in run.templates if "q14" in t)
    done = None
    try:
        run.start_reference()
        run.check_device()
        run.boot_and_load()
        if fault == "dropped_row":
            done = _drop_one_row_of_the_month(run, template)
        run.warm_up()
        if fault == "float32":
            _sums_in_float32(run, template)
        run.window()
        run.attach_audit()
        attempted, failed = run.compare()
        wrong = sorted({r["template"] for r in run.log
                        if r.get("correct") is False})
        return {"attempted": attempted, "failed": failed,
                "checks": run.checks, "fault": done, "templates": wrong}
    finally:
        run.stop_reference()
        if run.system is not None:
            run.system.close()
        run.budget.close()


def main(cell: str, seed: str, scale: str) -> int:
    out = {name: one_run(cell, seed, scale, fault)
           for name, fault in (("clean", None),
                               ("dropped_row", "dropped_row"),
                               ("float32", "float32"))}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:4]))
