#!/usr/bin/env python3
"""Run one cell of the benchmark on the accelerator; print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. The cell is an entry of ``workloads`` in
``BENCHMARK.json``; everything else is found by name under ``bench/``:
the configuration (``configs/<config>.json``), the traffic mix
(``traffic/<traffic>.json``, which names its runner,
``runners/<runner>.py``), the cell's correctness limits
(``limits/<cell>.json``) and one reader per per-layer metric
(``metrics/<metric>.py``). The harness itself is ``bench/harness.py``.

Off a TPU, or on a TPU kind that ``bench/peaks.json`` does not list, it
exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], root=ROOT, t0=T0))
