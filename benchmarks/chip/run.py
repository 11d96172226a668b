"""Run one cell of the chip benchmark once, from the repository root:

    python3 benchmarks/chip/run.py --workload housing.stream --seed 7 \\
        --seconds 30 --trace 0

The last line of standard output is the result, one JSON object; the
numbers that decide ``correct`` follow on standard error, each beside
its limit.  Without a TPU (or with fewer chips than the cell asks for)
the run exits non-zero and prints no result.
"""
import os
import sys
import time

T_PROCESS = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from benchmarks.chip import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_process=T_PROCESS))
