#!/usr/bin/env python3
"""Record a small profiler trace of a training cell's path on the chip.

    python bench/tools/trace_sample.py --workload otto.train \
        --rows 16384 --trees 4 --out OUT_DIR/trace_sample

Fits the cell's configuration cut to ``--rows`` rows and ``--trees``
trees inside a ``bench.window`` span under the profiler, copies the
``.xplane.pb`` to ``--out`` and prints each distinct device operation name
with its count and summed time, and the host span names: the way to see
how the kernels are named in a trace, and the recording that
``bench/tests/test_trace.py`` reads.
"""
import argparse
import collections
import glob
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="otto.train")
    ap.add_argument("--rows", type=int, default=16384)
    ap.add_argument("--trees", type=int, default=4)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    from harness import common, data, train
    from metrics import trace as TR
    cell = common.load_cell(args.workload)
    common.device_info(cell["chips"])
    conf = dict(cell["config"], n_train=args.rows, n_eval=args.rows // 4)
    cell = dict(cell, config=conf,
                traffic=dict(cell["traffic"], n_trees=args.trees))
    common.enable_caches()
    X, y, Xv, yv = data.train_eval(conf, 1)
    cfg = train.gbdt_config(cell, 1)
    train.fit(cfg, X, y, Xv, yv)
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with common.span("window"):
        for _ in range(2):
            with common.span("fit"):
                train.fit(cfg, X, y, Xv, yv)
    jax.profiler.stop_trace()
    os.makedirs(args.out, exist_ok=True)
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                    recursive=True)[0]
    shutil.copy(src, os.path.join(args.out, "sample.xplane.pb"))
    trace = TR.load(tmp)
    shutil.rmtree(tmp, ignore_errors=True)
    for chip, ops in trace.device.items():
        agg = collections.defaultdict(lambda: [0, 0.0])
        for s, e, n in ops:
            agg[n][0] += 1
            agg[n][1] += (e - s) * 1e-6
        print(f"chip {chip}: {len(ops)} ops, {len(agg)} names")
        for n, (c, ms) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
            print(f"  {ms:10.3f} ms {c:6d}x  {n}")
    names = collections.Counter(n for _, _, n in trace.host
                                if n.startswith("bench."))
    print("host spans:", dict(names))
    red = TR.reduce(trace)
    if red is not None:
        print(f"window_s={red.window_s} busy_s={red.busy_s}")
        print(red.breakdown())
    print("planes:", sorted(trace.device))
    return 0


if __name__ == "__main__":
    sys.exit(main())
