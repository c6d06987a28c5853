#!/usr/bin/env python3
"""Split a traced training window by the program's own scopes and spans.

    python bench/tools/scope_split.py --workload otto.train --seed 7 \
        --seconds 10 --out OUT_DIR/scope_split.json

Runs one ``--trace 1`` run of the cell through the harness's own
`harness.train.run` (the same set-up, window and check) and reads, from the
same trace and around the same window, what the harness does not pass to
the per-layer readers: every device operation's name-scope path
(`metrics.scopes.load`), and `repro.runtime.tracing.CompileCounter`
snapshots at the window's opening and closing.  It prints, and writes to
``--out`` as one JSON object:

* ``result``: the run's result line (end-to-end or per-layer metrics);
* ``split``: `metrics.scopes.round_split` (ms per round by scope, the
  unscoped rest of the scan program, binning per fit, other programs);
* ``spans_ms_per_fit``: each ``sketchboost.*`` host span per fit;
* ``idle_gaps``: the ten longest idle gaps with their owning span;
* ``device_ops``: the ten operations with the most own time, with their
  scope path and program;
* ``compiles``: set-up time (process start to window), counter snapshots
  at the window's opening and closing, the programs built in the window.

The hooks wrap `harness.common.start_trace` / `stop_trace` and
`metrics.trace.load` for the length of the run only.
"""
import argparse
import json
import os
import sys
import time

T0 = time.perf_counter()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def top_ops(owned, k: int = 10) -> list:
    """``[name, tf_op, program, seconds]`` of the ``k`` operations with the
    most own time, summed over their runs."""
    total = {}
    for op, t in owned:
        key = (op.name, op.tf_op, op.program)
        total[key] = total.get(key, 0.0) + t
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[*key, t * 1e-9] for key, t in ranked]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="otto.train")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    from harness import common, train
    from metrics import scopes as SC
    from metrics import trace as TR
    from repro.runtime import tracing

    counter = tracing.CompileCounter().start()
    seen = {}
    start_trace, stop_trace, load = (common.start_trace, common.stop_trace,
                                     TR.load)

    def start_hook():
        seen["setup_s"] = time.perf_counter() - T0
        seen["open"] = counter.snapshot()
        seen["open_programs"] = len(counter.names)
        return start_trace()

    def stop_hook(trace_dir):
        seen["close"] = counter.snapshot()
        seen["window_names"] = counter.names[seen["open_programs"]:]
        return stop_trace(trace_dir)

    def load_hook(trace_dir):
        seen["scoped"] = SC.load(trace_dir)
        seen["trace"] = load(trace_dir)
        return seen["trace"]

    common.start_trace, common.stop_trace, TR.load = (start_hook, stop_hook,
                                                      load_hook)
    try:
        cell = common.load_cell(args.workload)
        result, checks = train.run(cell, seed=args.seed,
                                   seconds=args.seconds, trace=True, t0=T0)
    finally:
        common.start_trace, common.stop_trace, TR.load = (
            start_trace, stop_trace, load)
        counter.stop()

    trace = seen["trace"]
    lo, hi = TR.window(trace)
    fits = result["attempted"]
    rounds = fits * cell["traffic"]["n_trees"]
    ops = seen["scoped"].get(0, [])
    window_ops = TR.clip([(o.start_ns, o.end_ns, o.name) for o in ops],
                         lo, hi)
    out = {
        "workload": args.workload, "seed": args.seed,
        "rounds": rounds, "fits": fits,
        "result": dict(result, checks=checks),
        "split": SC.round_split(ops, lo, hi, rounds, fits),
        "spans_ms_per_fit": {
            name: SC.span_ns(trace.host, name, lo, hi) * 1e-6 / fits
            for name in tracing.SPANS},
        "idle_gaps": SC.owned_gaps(window_ops, trace.host, lo, hi),
        "device_ops": top_ops(SC.own_times(ops, lo, hi)),
        "compiles": {"setup_s": seen["setup_s"],
                     "at_window_open": seen["open"],
                     "at_window_close": seen["close"],
                     "window_programs": seen["window_names"]},
    }
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
