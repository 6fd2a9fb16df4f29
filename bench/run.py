"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration, traffic mix and per-layer readers are files under
``bench/`` found by name. The system under test is the repository's
program under ``src/``. Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result. The last line of
standard output is the result as one JSON object.
"""
import os
import sys
import time

T_PROCESS = time.perf_counter()

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))
# libtpu writes its logs to a fixed directory under /tmp unless told
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from harness.main import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], T_PROCESS))
