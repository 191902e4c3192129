#!/usr/bin/env python3
"""``python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: run one cell of ``BENCHMARK.json`` on the chip this
machine holds. The last line of standard output is the result object;
without a TPU (or with fewer chips than the cell asks for) the exit code
is not 0 and no result is printed. See ``benchmarks/harness.py``."""

import time

_T_START = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks import harness

    sys.exit(harness.main(None, _T_START))
