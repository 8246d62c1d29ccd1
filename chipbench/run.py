#!/usr/bin/env python3
"""Benchmark entry point: one cell, one seed, one measured window.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs on the machine it is started on, in one process that holds the chip(s),
and exits non-zero without a result when JAX finds no TPU or fewer chips than
the cell asks for.  The last line of standard output is the result object.
"""

import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# libtpu logs under /tmp/tpu_logs unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from chipbench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=T0))
