"""Run one benchmark cell once on the chip this process finds:

  python3 benchmarks/chip/run_cell.py --workload <name> --seed <n> \
      --seconds <s> --trace <0|1>

Prints the result as one JSON line, last on standard output, and the
numbers compared for ``correct`` with their limits as the last lines of
standard error. Without a TPU, or with fewer chips than the cell asks
for, or without the program beside it, it exits non-zero and prints no
result.
"""
import os
import sys
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
# libtpu logs under /tmp unless told otherwise; write nothing outside
# the checkout and the given HOME / TMPDIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")
# import the benchmark as the package ``chip`` (benchmarks/ on the path),
# never its modules by their bare names
sys.path[0] = os.path.dirname(HERE)

from chip.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=T_PROCESS))
