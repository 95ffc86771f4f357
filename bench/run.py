"""Benchmark entry point.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  The last line of standard output is the run's result as
one JSON object; see ``bench/harness.py``."""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout's root and the program's sources, and not bench/ itself,
# whose module names are not meant to be importable at top level
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
