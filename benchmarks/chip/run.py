"""Chip benchmark of the P2P train step: one run of one cell.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on and refuses to run without a TPU (or
with fewer chips than the cell asks for): it then exits non-zero and
prints no result. Otherwise the last line of standard output is one JSON
object; see chipbench/harness.py.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE)]

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
