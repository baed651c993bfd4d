"""Driven by ``test_write_mix.py`` in a process of its own: ``run.py`` with
the contract's limits patched to a few seconds.

    python3 benchmark/tests/drive_short_limit.py <limit_s> <run.py's arguments>
"""

import os
import sys
import time

T_START = time.monotonic()
sys.path.insert(0, os.getcwd())

from benchmark.harness import budget  # noqa: E402

if __name__ == "__main__":
    budget.CONTRACT_S = budget.FIRST_RUN_S = float(sys.argv[1])
    budget.MARGIN_S = 1.0
    from benchmark.harness.runner import main

    sys.exit(main(sys.argv[2:], T_START))
