"""Run one benchmark cell once on the chip and print one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json``.  Set-up (compile
included) runs first and is reported as ``setup_s``; then the cell's
traffic runs for ``--seconds``; then what the window produced is
compared with the plain references of ``bench/reference``.  The last
line of standard output is the result object; the numbers compared,
each with its limit, are the last lines of standard error and the
result's last key, ``checks``.  With ``--trace 1`` the window runs
under the JAX profiler and the per-layer metrics are reported instead
of the end-to-end ones.  Exits 3, printing no result, where JAX finds
no TPU or fewer chips than the cell asks for.
"""
import sys
import time
from pathlib import Path

STARTED = time.perf_counter()
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(harness.main(started=STARTED))
