"""One run of one cell of the benchmark.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell asks
for. The last line of standard output is the result; see benchmarks/README.md.
"""

import time

_STARTED = time.perf_counter()  # set-up is counted from this line

import os  # noqa: E402
import sys  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

if __name__ == "__main__":
    from benchmarks.harness.loop import main

    sys.exit(main(sys.argv[1:], _ROOT, _STARTED))
