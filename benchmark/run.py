"""Run one benchmark cell on the chip:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The last line of standard output is the result (JSON); the numbers the
correctness check compared, each with its limit, are the last lines of
standard error. With no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result. There is no CPU branch."""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

if __name__ == "__main__":
    from benchmark.harness import runner

    sys.exit(runner.main(sys.argv[1:], T_START))
