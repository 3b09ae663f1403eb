#!/usr/bin/env python3
"""Readings of a cell's sound runs, its control and its planted faults.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \\
        --modes program,control,half_batch

For each seed and mode it prints one JSON line with the numbers the cell
compares, the limits of ``bench/limits/<cell>.json`` are set from them:

* ``program``: the system under test, run as the benchmark runs it, with
  a window of ``--seconds`` (the lower readings);
* any other mode is the runner's ``control(ctx, mode)``: the plain
  reference put in the program's place in a lower precision
  (``control``), or with a fault planted in it (the upper readings).

The benchmark's own runs never run this. Run it on the chip, at the cell's
own size; ``--cpu`` lets it run off a TPU (the tests do).
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def readings(root: str, workload: str, seeds, modes, seconds: float,
             require_chip: bool = True):
    """Yield ``{"seed", "mode", <number>: value, ...}`` per seed and mode."""
    for seed in seeds:
        for mode in modes:
            ctx = harness.open_context(root, workload, seed, seconds, False,
                                       time.perf_counter(), require_chip)
            runner = harness.load_runner(ctx)
            if mode == "program":
                nums = {c.name: c.value for c in runner.run(ctx).verify()}
            else:
                nums = runner.control(ctx, mode)
            yield {"seed": seed, "mode": mode, **nums}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="program,control")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        for line in readings(ROOT, args.workload, seeds,
                             args.modes.split(","), args.seconds,
                             require_chip=not args.cpu):
            print(json.dumps(line), flush=True)
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
