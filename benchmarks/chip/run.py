"""Run one benchmark cell once, in this process, on the chips JAX finds.

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix, its limits and its per-layer
metric readers are all found by name from ``BENCHMARK.json`` at the root of
the checkout; see ``harness.py``.  The last line on stdout is the result,
one JSON object.  Without a TPU, or with fewer chips than the cell asks
for, nothing runs and the exit code is not 0.
"""

import time

PROCESS_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], process_start=PROCESS_START))
