"""GBDT serving launcher: checkpointed PackedForest -> batched request driver.

Loads a serving checkpoint written by `io.checkpoint.save_forest_checkpoint`
(or trains + checkpoints a synthetic demo model with ``--demo``), stands up a
`training.serve_lib.ForestServer`, and drives a simulated request stream
through it in micro-batched windows, reporting latency percentiles and
throughput — the smoke-level stand-in for a real RPC front end.

With ``--explain`` the same driver exercises the explanation serving path:
micro-batched TreeSHAP over the request stream (per-request latency), plus a
top-k attribution report and checkpoint-only feature importances.

With ``--chaos`` the driver instead runs the overload/admission smoke: a
deterministic burst (virtual clock, no sleeping) that forces queue shedding,
deadline drops, and fallback-forest scoring, then asserts every degradation
counter fired and writes the stats to ``--stats-out`` — the CI artifact
proving the server degrades instead of falling over (docs/robustness.md).

  PYTHONPATH=src python -m repro.launch.serve --demo --requests 64
  PYTHONPATH=src python -m repro.launch.serve --ckpt /ckpts/otto --requests 256
  PYTHONPATH=src python -m repro.launch.serve --demo --explain --topk 5
  PYTHONPATH=src python -m repro.launch.serve --demo --chaos \
      --stats-out results/serve_chaos.json
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def _train_demo(ckpt_dir: str, seed: int):
    """Train a small synthetic multiclass model and checkpoint it."""
    from repro.core.boosting import GBDTConfig, SketchBoost
    from repro.data.pipeline import make_tabular
    from repro.io.checkpoint import save_forest_checkpoint

    X, y = make_tabular("multiclass", 4000, 20, 6, seed=seed)
    cfg = GBDTConfig(loss="multiclass", sketch_method="random_projection",
                     sketch_k=3, n_trees=40, depth=5, learning_rate=0.1,
                     seed=seed)
    t0 = time.perf_counter()
    model = SketchBoost(cfg).fit(X, y)
    print(f"[serve] demo model trained in {time.perf_counter() - t0:.1f}s "
          f"({model.packed.n_trees} trees, depth {model.packed.depth})")
    save_forest_checkpoint(ckpt_dir, model.packed, model.quantizer,
                           metadata={"loss": cfg.loss,
                                     "n_features": X.shape[1]})
    print(f"[serve] checkpoint written to {ckpt_dir}")
    return X.shape[1]


def _chaos_smoke(args) -> None:
    """Deterministic overload drill: overwhelm the admission queue, expire a
    deadline on the virtual clock, trip the fallback forest, and fail loudly
    unless every degradation path both fired and kept serving."""
    from repro.runtime.chaos import VirtualClock
    from repro.training.serve_lib import ForestServeConfig, ForestServer

    clock = VirtualClock()
    server = ForestServer.from_checkpoint(
        args.ckpt, max_batch=args.max_batch, max_queue_rows=4 * args.rows,
        deadline_ms=50.0, overload_rows=2 * args.rows, clock=clock)
    m = args.features or server.quantizer.edges.shape[0]
    rng = np.random.default_rng(args.seed)
    reqs = [rng.normal(size=(args.rows, m)).astype(np.float32)
            for _ in range(max(8, args.requests))]

    # Burst 1: six requests into a four-request queue -> two shed; the four
    # admitted rows exceed overload_rows -> fallback-forest scoring.
    admitted = [server.submit(r) for r in reqs[:6]]
    outs = server.drain()
    served = sum(o is not None for o in outs)
    # Burst 2: admit two, expire one on the virtual clock before draining.
    server.submit(reqs[6], deadline_ms=10.0)
    server.submit(reqs[7], deadline_ms=500.0)
    clock.advance(0.1)
    outs2 = server.drain()

    s = server.stats
    print(f"[serve-chaos] admitted={sum(admitted)}/6 served={served} "
          f"shed={s['shed_requests']} deadline={s['deadline_requests']} "
          f"fallback_batches={s['fallback_batches']} errors={s['errors']}")
    ok = (s["shed_requests"] == 2 and s["deadline_requests"] == 1
          and s["fallback_batches"] >= 1 and s["errors"] == 0
          and served == 4 and outs2[0] is None and outs2[1] is not None)
    if args.stats_out:
        os.makedirs(os.path.dirname(args.stats_out) or ".", exist_ok=True)
        with open(args.stats_out, "w") as f:
            json.dump({"ok": ok, "stats": s,
                       "best_iteration": server.best_iteration,
                       "fallback_rounds": server._fallback_packed().n_rounds},
                      f, indent=1)
        print(f"[serve-chaos] stats written to {args.stats_out}")
    if not ok:
        raise SystemExit(f"[serve-chaos] FAIL: degradation counters off: {s}")
    print("[serve-chaos] OK: shed, deadline-drop, and fallback paths all "
          "fired; no errors")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ckpt", default="/tmp/repro_serve_gbdt",
                    help="serving checkpoint directory")
    ap.add_argument("--demo", action="store_true",
                    help="train + checkpoint a synthetic model first")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--rows", type=int, default=32,
                    help="rows per request (feature blocks)")
    ap.add_argument("--window", type=int, default=8,
                    help="requests micro-batched per forest pass")
    ap.add_argument("--features", type=int, default=0,
                    help="request feature count (default: from metadata)")
    ap.add_argument("--max-batch", type=int, default=4096)
    ap.add_argument("--prune-alpha", type=float, default=None,
                    help="cost-complexity post-pruning threshold (0.0 "
                         "removes gainless splits; default: no pruning)")
    ap.add_argument("--quantize", default="none",
                    choices=("none", "bfloat16", "int8"),
                    help="leaf-block storage dtype (thresholds stay "
                         "split-exact uint8 bin codes)")
    ap.add_argument("--max-buckets", type=int, default=0,
                    help="LRU cap on padded-batch compile buckets "
                         "(0 = unbounded)")
    ap.add_argument("--double-buffer", action="store_true",
                    help="overlap host->device copies with traversal on "
                         "streamed oversize batches")
    ap.add_argument("--explain", action="store_true",
                    help="also drive the SHAP explanation endpoint and "
                    "print a top-k attribution report")
    ap.add_argument("--topk", type=int, default=3,
                    help="features per output in the --explain report")
    ap.add_argument("--chaos", action="store_true",
                    help="run the deterministic overload/admission smoke "
                         "instead of the throughput driver")
    ap.add_argument("--stats-out", default="",
                    help="write the --chaos stats artifact (JSON) here")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.demo:
        _train_demo(args.ckpt, args.seed)

    if args.chaos:
        _chaos_smoke(args)
        return

    from repro.training.serve_lib import ForestServer
    server = ForestServer.from_checkpoint(
        args.ckpt, max_batch=args.max_batch, prune_alpha=args.prune_alpha,
        quantize=args.quantize, max_buckets=args.max_buckets,
        double_buffer=args.double_buffer)
    if server.quantizer is None:
        ap.error(f"checkpoint {args.ckpt} has no quantizer; this driver "
                 "sends raw float features (re-save with the quantizer, or "
                 "serve pre-binned codes via ForestServer.predict_codes)")
    meta_m = args.features or server.quantizer.edges.shape[0]
    print(f"[serve] loaded forest: {server.packed.n_trees} trees, "
          f"depth {server.packed.depth}, d={server.packed.n_outputs}, "
          f"kernel mode {server.mode!r}")
    comp = server.compression
    if comp["prune_alpha"] is not None or comp["quantize"] != "none":
        print(f"[serve] compression: {comp['nodes_before']} -> "
              f"{comp['nodes_after']} nodes, depth {comp['depth_before']} "
              f"-> {comp['depth_after']}, {comp['bytes_before']:,} -> "
              f"{comp['bytes_after']:,} bytes "
              f"(prune_alpha={comp['prune_alpha']}, "
              f"quantize={comp['quantize']})")

    rng = np.random.default_rng(args.seed)
    requests = [rng.normal(size=(args.rows, meta_m)).astype(np.float32)
                for _ in range(args.requests)]
    # Warm the compile cache on one window, then zero the counters so the
    # reported throughput is steady-state only.
    server.serve(requests[:args.window])
    server.reset_stats()

    lat = []
    t0 = time.perf_counter()
    for ofs in range(0, len(requests), args.window):
        w0 = time.perf_counter()
        outs = server.serve(requests[ofs:ofs + args.window])
        lat.extend([(time.perf_counter() - w0) * 1e3] * len(outs))
    wall = time.perf_counter() - t0

    lat = np.asarray(lat)
    n_rows = args.requests * args.rows
    print(f"[serve] {args.requests} requests x {args.rows} rows in "
          f"{wall:.2f}s  ({n_rows / wall:,.0f} rows/s end-to-end, "
          f"{server.throughput():,.0f} rows/s in-predict)")
    print(f"[serve] latency/request: p50 {np.percentile(lat, 50):.2f}ms  "
          f"p99 {np.percentile(lat, 99):.2f}ms  "
          f"(window={args.window}, max_batch={args.max_batch})")

    if args.explain:
        if not server.explainable:
            ap.error(f"checkpoint {args.ckpt} predates cover packing "
                     "(format_version 1): --explain unavailable; re-save "
                     "the checkpoint from a freshly trained model")
        server.serve_explain(requests[:args.window])       # warm compile
        server.stats["explain_requests"] = 0               # steady-state only
        server.stats["explain_rows"] = 0
        server.stats["explain_time_s"] = 0.0
        elat = []
        for ofs in range(0, len(requests), args.window):
            w0 = time.perf_counter()
            outs = server.serve_explain(requests[ofs:ofs + args.window])
            elat.extend([(time.perf_counter() - w0) * 1e3] * len(outs))
        elat = np.asarray(elat)
        erate = (server.stats["explain_rows"]
                 / max(server.stats["explain_time_s"], 1e-9))
        print(f"[serve] explain latency/request: "
              f"p50 {np.percentile(elat, 50):.2f}ms  "
              f"p99 {np.percentile(elat, 99):.2f}ms  "
              f"({erate:,.0f} rows/s in-shap)")
        phi, base = outs[-1]                               # last window
        row_phi = phi[0]                                   # (m, d)
        for j in range(row_phi.shape[1]):
            order = np.argsort(-np.abs(row_phi[:, j]))[:args.topk]
            feats = ", ".join(f"x{f}={row_phi[f, j]:+.4f}" for f in order)
            print(f"[serve]   output {j}: base {base[j]:+.4f}  top "
                  f"{args.topk}: {feats}")
        imp = server.feature_importances("gain")
        order = np.argsort(-imp)[:args.topk]
        print("[serve] global gain importances: "
              + ", ".join(f"x{f}={imp[f]:.3f}" for f in order))


if __name__ == "__main__":
    main()
