"""Run one benchmark cell once on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells are the ``workloads`` of BENCHMARK.json. The last line of standard
output is the run's result, one JSON object; the numbers that decided its
``correct`` are the last lines of standard error. Without a TPU, or with
fewer chips than the cell asks for, it prints no result and exits 3.
"""
import time

T0 = time.perf_counter()        # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
