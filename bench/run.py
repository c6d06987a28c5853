#!/usr/bin/env python3
"""SketchBoost chip benchmark: one run of one cell.

    python bench/run.py --workload otto.train --seed 7 --seconds 10 --trace 0

Runs from the root of a checkout on a machine that holds the chips the
cell asks for, and refuses (exit code 3, no result) where JAX finds no TPU
or too few chips.  Everything a cell needs is found by name from
``BENCHMARK.json``: its configuration (``bench/configs/<config>.json``),
its traffic (``bench/traffic/<traffic>.json``, whose ``kind`` picks the
runner in ``bench/harness``), the limits of its check
(``bench/limits/<workload>.json``) and its per-layer metrics
(``bench/metrics/<metric>.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a profiler trace of the window.
Both check what the timed path produced against the plain reference in
``bench/configs/reference.py``; the last lines of standard error give each
compared number beside its limit, and the last line of standard output is
the result as one JSON object.
"""
import time

T0 = time.perf_counter()        # set-up is timed from process start

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from harness import common, train
    cell = common.load_cell(args.workload)
    runners = {"train": train.run}
    result, checks = runners[cell["traffic"]["kind"]](
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t0=T0)
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
