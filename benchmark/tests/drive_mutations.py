"""Driven by ``test_write_mix.py`` in a process of its own, from the root of
a copy that lists a cell whose mix writes: one rehearsal of the cell through
the runner's own phases, then three faults that ``correct`` has to catch.
Prints one JSON line: the clean run's counts and each fault's.

    python3 benchmark/tests/drive_mutations.py <cell> <seed> <scale>
"""

import json
import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.getcwd())

from benchmark.harness import runner  # noqa: E402


def main(cell: str, seed: str, scale: str) -> int:
    run = runner.Run(runner.parse_args([
        "--workload", cell, "--seed", seed, "--seconds", "1.5", "--trace",
        "0", "--rehearse", scale]), T_START)
    out = {}
    try:
        run.start_reference()
        run.check_device()
        run.boot_and_load()
        run.warm_up()
        run.window()
        run.read_back_tables()
        run.attach_audit()
        out["clean"] = run.compare()
        log = run.log
        window = [i for i, r in enumerate(log) if r["phase"] == "window"]
        written = [i for i in window if log[i]["sql"] is None]
        out["window"] = {"writes": len(written),
                         "reads": len(window) - len(written)}

        # 1. an acknowledged write dropped from the replay
        rec = log[written[0]]
        acks, rec["acks"] = rec["acks"], [False] * len(rec["acks"])
        out["dropped_write"] = run.compare()
        rec["acks"] = acks

        # 2. a write and the read after it swapped in the log: the first
        # pair whose write changes that read's answer
        out["swapped"], tried = None, 0
        for i in written:
            if i + 1 not in window or log[i + 1]["sql"] is None:
                continue
            tried += 1
            for seq in (run.log, run.answers):
                seq[i], seq[i + 1] = seq[i + 1], seq[i]
            got = run.compare()
            for seq in (run.log, run.answers):
                seq[i], seq[i + 1] = seq[i + 1], seq[i]
            if got[1]:
                out["swapped"] = got
                break
        out["pairs_tried"] = tried

        # 3. a row removed behind the harness's back, before the read-back
        table, (key, _) = next(iter(run.read_back_columns.items()))
        top = run.system.fetch(run.system.execute(
            f"select max({key}) as k from {table}")).arrays["k"][0]
        run.system.execute(f"delete from {table} where {key} = {int(top)}")
        run.read_back_tables()
        out["lost_row"] = run.compare()
        out["checks"] = run.checks
    finally:
        run.stop_reference()
        if run.system is not None:
            run.system.close()
    run.budget.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:4]))
