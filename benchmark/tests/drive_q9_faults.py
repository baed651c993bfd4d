"""Driven by ``test_sf10_part4_cell.py`` in a process of its own: rehearsals
of a cell that holds Q9 to its exact reference
(``references/tpch_q9_exact``), run over the same phases three times, all
with every join that is not partition-wise made to move rows (nothing is
small enough to broadcast at a rehearsal's scale otherwise).  The first run
is clean.  In the second every PKEY exchange loses ONE row a shard: the
first live lane of what it received goes dead, as an exchange that dropped
a row without counting it would leave it.  In the third the program sums
Q9's profit in float32 (the statement reaches it with ``amount`` cast to
FLOAT).  Prints one JSON line with the three runs' counts: both faults
have to make ``exact_differ`` non-zero, since the sums are compared bit
for bit.

    python3 benchmark/tests/drive_q9_faults.py <cell> <seed> <scale>
"""

import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.getcwd())

from benchmark.harness import runner  # noqa: E402

AMOUNT = ("l_extendedprice * (1 - l_discount) - ps_supplycost * l_quantity "
          "as amount")


def _move_rows():
    from oceanbase_tpu.px import planner

    planner.BROADCAST_THRESHOLD_BYTES = 0
    return planner


def _lose_a_row_in_every_pkey_exchange(planner):
    """-> undo.  What a PKEY exchange received loses its first live lane
    on every shard; the overflow count stays 0."""
    import jax.numpy as jnp

    real = planner.all_to_all_repartition

    def lossy(rel, keys, ndev, cap, axis, kind=None):
        recv, ovf = real(rel, keys, ndev, cap, axis, kind=kind)
        if kind == "pkey":
            m = recv.mask_or_true()
            first = m & (jnp.cumsum(m.astype(jnp.int32)) == 1)
            recv = recv.with_mask(m & ~first)
        return recv, ovf

    planner.all_to_all_repartition = lossy
    return lambda: setattr(planner, "all_to_all_repartition", real)


def _profit_in_float32(run, template: str):
    real = run.system.execute
    mine = {it.sql for it in run.items[template]}

    def execute(sql):
        if sql in mine:
            assert AMOUNT in sql
            sql = sql.replace(AMOUNT, "cast(" + AMOUNT[:-len(" as amount")]
                              + " as float) as amount")
        return real(sql)

    run.system.execute = execute


def one_run(cell: str, seed: str, scale: str, fault: str | None) -> dict:
    run = runner.Run(runner.parse_args([
        "--workload", cell, "--seed", seed, "--seconds", "2", "--trace",
        "0", "--rehearse", scale]), time.monotonic())
    template = next(t for t in run.templates if "q9" in t)
    undo = None
    try:
        run.start_reference()
        run.check_device()
        planner = _move_rows()
        run.boot_and_load()
        if fault == "lost_row":
            undo = _lose_a_row_in_every_pkey_exchange(planner)
        run.warm_up()
        if fault == "float32":
            _profit_in_float32(run, template)
        run.window()
        moved = sum(v for k, v in run.system.counters().items()
                    if k.startswith("px.exchange_rows{kind=pkey}"))
        run.attach_audit()
        attempted, failed = run.compare()
        wrong = sorted({r["template"] for r in run.log
                        if r.get("correct") is False})
        return {"attempted": attempted, "failed": failed,
                "checks": run.checks, "templates": wrong,
                "pkey_rows": moved}
    finally:
        if undo is not None:
            undo()
        run.stop_reference()
        if run.system is not None:
            run.system.close()
        run.budget.close()


def main(cell: str, seed: str, scale: str) -> int:
    out = {name: one_run(cell, seed, scale, fault)
           for name, fault in (("clean", None), ("lost_row", "lost_row"),
                               ("float32", "float32"))}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:4]))
