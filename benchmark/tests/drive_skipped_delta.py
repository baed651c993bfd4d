"""Driven by ``test_htap_cell.py`` in a process of its own: one rehearsal of
a cell whose mix writes, run TWICE over the same phases.  The first run is
clean.  In the second the program loses one committed delta: the first time
the window's read finds its resident relation behind the newest commit, the
relation is handed on as it was and marked current, as a maintained copy
that skipped an apply would be.  Prints one JSON line with both runs'
counts: ``correct`` must be held by the replayed reference, not by a
median.

    python3 benchmark/tests/drive_skipped_delta.py <cell> <seed> <scale>
"""

import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.getcwd())

from benchmark.harness import runner  # noqa: E402


def one_run(cell: str, seed: str, scale: str, skip: bool) -> dict:
    run = runner.Run(runner.parse_args([
        "--workload", cell, "--seed", seed, "--seconds", "4", "--trace",
        "0", "--rehearse", scale]), time.monotonic())
    try:
        run.start_reference()
        run.check_device()
        run.boot_and_load()
        run.warm_up()
        skipped = []
        if skip:
            from oceanbase_tpu.storage import engine

            real = engine.apply_delta

            def lossy(copy, delta, key_cols):
                if not skipped and delta.row_keys:
                    skipped.append(len(delta.row_keys))
                    return copy.rel, copy.high, copy.live, 0, 0
                return real(copy, delta, key_cols)

            engine.apply_delta = lossy
        try:
            run.window()
        finally:
            if skip:
                engine.apply_delta = real
        run.read_back_tables()
        run.attach_audit()
        attempted, failed = run.compare()
        return {"attempted": attempted, "failed": failed,
                "checks": run.checks, "skipped_rows": skipped}
    finally:
        run.stop_reference()
        if run.system is not None:
            run.system.close()
        run.budget.close()


def main(cell: str, seed: str, scale: str) -> int:
    out = {"clean": one_run(cell, seed, scale, skip=False),
           "skipped": one_run(cell, seed, scale, skip=True)}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:4]))
