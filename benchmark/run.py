"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One run of one cell of BENCHMARK.json on the chips of this machine; the
last line of the standard output is the result (see benchmark/README.md).
"""

import time

T_START = time.monotonic()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

# the repository's root, so that ``benchmark`` (and, from the adapter alone,
# the program) import wherever the checkout is
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmark.harness.runner import main

    sys.exit(main(sys.argv[1:], T_START))
