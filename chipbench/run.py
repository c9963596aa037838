"""Run one cell of the chip benchmark on the machine this starts on.

    python3 chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

See ``chipbench/harness/cli.py``.
"""
import time

T0 = time.perf_counter()

import os   # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench.harness.cli import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T0))
