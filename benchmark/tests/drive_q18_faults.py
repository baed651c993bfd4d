"""Driven by ``test_sf10_orders_cell.py`` in a process of its own, from a
COPY of the benchmark: rehearsals of a cell that holds Q4, Q13 and Q18 to
their exact references, run over the same phases four times.  The first
run is clean.  Each of the others serves a lower guarantee than the
configuration states, and the comparison has to find every one:

- ``lost_row``: after the load one ``lineitem`` row of an order that
  passes Q18's HAVING is deleted from the served tables (the reference
  keeps the seed's data): that order's sum falls, or the order drops out;
- ``weaker_statements``, three at once, each in its own template: Q18
  compares ``>=`` where the statement says ``>`` (orders at exactly the
  threshold come in), Q4's semi-join is served as a join (an order counts
  once a late LINE), Q13's outer join as an inner one (the customers
  without orders, the row ``c_count = 0``, are gone);
- ``count_star``: Q13 counts ``count(*)`` where the statement counts
  ``count(o_orderkey)`` (a customer without orders counts one).

Before the runs the copy's Q18 statement gets a QUANTITY at which the
seed's data HAS an order at exactly the threshold and orders over it (the
second largest sum any order reaches): at a rehearsal's scale no order
need stand at exactly 300.  Prints one JSON line with the four runs'
counts.

    python3 benchmark/tests/drive_q18_faults.py <cell> <seed> <scale>
"""

import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.getcwd())

import numpy as np  # noqa: E402

from benchmark.harness import runner, spec  # noqa: E402


def _threshold_with_an_order_on_it(cell, seed: int, scale: float):
    """-> (QUANTITY, an order over it, as many of its lines as it has)."""
    ds = spec.load_module("datasets", cell.config["dataset"]["generator"],
                          cell.bench_dir)
    tables, _types = ds.generate(scale, seed)
    li = tables["lineitem"]
    sums = np.bincount(li["l_orderkey"], weights=li["l_quantity"]) \
        .astype(np.int64)
    top = np.unique(sums)[-2:]
    assert len(top) == 2 and top[0] % 100 == 0
    return int(top[0]) // 100, int(np.flatnonzero(sums == top[1])[0])


def _set_quantity(cell, quantity: int):
    path = os.path.join(cell.bench_dir, "statements", "tpch_q18_sf10.json")
    st = spec.read_json(path)
    st["parameters"]["QUANTITY"].update(validation=quantity,
                                        min=min(quantity, 300))
    with open(path, "w", encoding="utf-8") as f:
        json.dump(st, f)


REWRITES = {
    "weaker_statements": [
        ("tpch_q18_sf10", "having sum(l_quantity) > ",
         "having sum(l_quantity) >= "),
        ("tpch_q4_sf10",
         "and exists (select * from lineitem where l_orderkey = o_orderkey "
         "and l_commitdate < l_receiptdate)",
         "and l_orderkey = o_orderkey and l_commitdate < l_receiptdate"),
        ("tpch_q4_sf10", "from orders where", "from orders, lineitem where"),
        ("tpch_q13_sf10", "customer left outer join orders",
         "customer join orders")],
    "count_star": [
        ("tpch_q13_sf10", "count(o_orderkey)", "count(*)")],
}


def _serve_weaker(run, rewrites):
    real = run.system.execute
    template_of = {it.sql: t for t, its in run.items.items() for it in its}

    def execute(sql):
        for template, old, new in rewrites:
            if template_of.get(sql) == template:
                assert old in sql, (template, old)
                template_of[sql.replace(old, new)] = template
                sql = sql.replace(old, new)
        return real(sql)

    run.system.execute = execute


def one_run(cell: str, seed: str, scale: str, fault: str | None,
            order: int) -> dict:
    run = runner.Run(runner.parse_args([
        "--workload", cell, "--seed", seed, "--seconds", "1", "--trace",
        "0", "--rehearse", scale]), time.monotonic())
    try:
        run.start_reference()
        run.check_device()
        run.boot_and_load()
        if fault == "lost_row":
            run.system.execute(f"delete from lineitem where l_orderkey = "
                               f"{order} and l_linenumber = 1")
        elif fault is not None:
            _serve_weaker(run, REWRITES[fault])
        run.warm_up()
        run.window()
        run.attach_audit()
        attempted, failed = run.compare()
        wrong = sorted({r["template"] for r in run.log
                        if r.get("correct") is False})
        return {"attempted": attempted, "failed": failed,
                "checks": run.checks, "templates": wrong}
    finally:
        run.stop_reference()
        if run.system is not None:
            run.system.close()
        run.budget.close()


def main(cell: str, seed: str, scale: str) -> int:
    quantity, order = _threshold_with_an_order_on_it(
        spec.Cell(cell), int(seed), float(scale))
    _set_quantity(spec.Cell(cell), quantity)
    out = {"quantity": quantity, "order": order}
    for name in ("clean", "lost_row", "weaker_statements", "count_star"):
        out[name] = one_run(cell, seed, scale,
                            None if name == "clean" else name, order)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:4]))
