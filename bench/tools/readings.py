#!/usr/bin/env python3
"""Readings of a training cell's check, for setting its limits.

    python bench/tools/readings.py --workload otto.train \
        --variants program control half_batch --seeds 11 12 13

Variants: ``program`` (the fit as the benchmark runs it), ``control``
(the program with its lower-precision path switched on: bfloat16
histogram statistics) and the faults of `harness.faults`.  Each variant
fits the cell's job once per seed at the cell's size, in this one
process, and checks it.  One JSON line per variant and seed on standard
output, with the chip's memory statistics after the fit.
"""
import argparse
import contextlib
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def reading(cell, seed, variant):
    import jax
    from harness import common, data, faults, train
    seed31, _ = common.seed_parts(seed)
    X, y, Xv, yv = data.train_eval(cell["config"], seed)
    over = {"hist_dtype": "bfloat16"} if variant == "control" else {}
    cfg = train.gbdt_config(cell, seed31, **over)
    ctx = (faults.planted(variant) if variant in faults.TRAIN_FAULTS
           else contextlib.nullcontext())
    t = time.perf_counter()
    with ctx:
        model = train.fit(cfg, X, y, Xv, yv)
    fit_s = time.perf_counter() - t
    ans = train.answer(model)
    del model
    mem = jax.devices()[0].memory_stats() or {}
    t = time.perf_counter()
    checks = train.check(cell, ans, X, y, Xv, yv, seed31, seed)
    return checks, {"fit_s": fit_s, "check_s": time.perf_counter() - t,
                    "memory_stats": mem}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variants", nargs="+", default=["program"])
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from harness import common
    cell = common.load_cell(args.workload)
    dev = common.device_info(cell["chips"])
    common.enable_caches()
    for variant in args.variants:
        for seed in args.seeds:
            checks, info = reading(cell, seed, variant)
            print(json.dumps({"workload": args.workload, "variant": variant,
                              "seed": seed, "kind": dev["kind"],
                              "readings": {k: c["value"]
                                           for k, c in checks.items()},
                              **info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
