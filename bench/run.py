"""Run one benchmark cell and print its result as the last line.

    python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

from the root of a checkout.  See ``harness.py``.
"""
import time

T_START = time.perf_counter()  # set-up is timed from here

import sys  # noqa: E402

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T_START))
