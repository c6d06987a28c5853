"""Benchmark harness — one function per paper table/figure.

Paper: SketchBoost (NeurIPS 2022).  Real datasets are not available offline,
so every table runs on the paper's own synthetic protocol (App. B.7, Guyon
2003 generator) at reduced scale; the *relative* comparisons (sketch vs Full
vs one-vs-all, time vs d) are the reproduction targets.

  table1   quality: test loss per sketch method x k       (paper Table 1/10)
  table2   training time per sketch method x k            (paper Table 2/12)
  fig1     time vs output dimension d                     (paper Fig. 1/4)
  fig3     learning curves full vs sketch                 (paper Fig. 3)
  rounds   boosting rounds to convergence                 (paper Table 13)
  hist     histogram-engine microbench: direct vs partitioned vs sibling
           subtraction per tree depth                     (-> BENCH_hist.json)
  predict  packed-forest inference baseline               (-> BENCH_predict.json)
  serve    serving tier: compression x quantization matrix (-> BENCH_serve.json)
  shap     TreeSHAP explanation-serving baseline          (-> BENCH_shap.json)
  kernels  Pallas kernel vs jnp oracle timings (CPU interpret; structural)
  compression  sketched vs exact DP all-reduce bytes      (beyond-paper)

`python -m benchmarks.run` runs everything at quick scale and writes
results/bench_*.json + a CSV summary to stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Any, Dict, List

import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results")

QUICK = dict(n=6000, m=40, trees=60, depth=5, es=20)
FULL = dict(n=60000, m=80, trees=300, depth=6, es=50)
SMOKE = dict(n=800, m=10, trees=10, depth=4, es=0)     # CI-speed shapes


def _cfg(loss, method, k, scale, seed=0, **kw):
    from repro.core.boosting import GBDTConfig
    return GBDTConfig(loss=loss, sketch_method=method, sketch_k=k,
                      n_trees=scale["trees"], depth=scale["depth"],
                      learning_rate=0.1, seed=seed,
                      early_stopping_rounds=scale["es"], **kw)


def _fit_eval(task, loss, method, k, d, scale, seed=0, strategy="single_tree"):
    import jax
    from repro.core.boosting import SketchBoost
    from repro.data.pipeline import make_tabular, train_test_split
    X, y = make_tabular(task, scale["n"], scale["m"], d, seed=seed)
    Xtr, Xte, ytr, yte = train_test_split(X, y, seed=seed)
    cut = int(len(Xtr) * 0.85)
    cfg = _cfg(loss, method, k, scale, seed=seed, strategy=strategy)
    t0 = time.perf_counter()
    model = SketchBoost(cfg).fit(Xtr[:cut], ytr[:cut],
                                 eval_set=(Xtr[cut:], ytr[cut:]))
    jax.block_until_ready(model.forest.value)
    dt = time.perf_counter() - t0
    return {"task": task, "method": method, "k": k, "d": d,
            "strategy": strategy,
            "test_loss": model.eval_loss(Xte, yte),
            "rounds": model.forest.n_trees, "time_s": round(dt, 2)}


TASKS = [("multiclass", "multiclass", 9),       # Otto-like
         ("multilabel", "multilabel", 24),      # MoA-like (reduced)
         ("multitask_mse", "multitask_mse", 16)]  # SCM20D-like


def bench_table1(scale) -> List[Dict]:
    """Quality: every sketch method (best k behaviour) vs Full vs one-vs-all."""
    rows = []
    for task, loss, d in TASKS:
        rows.append(_fit_eval(task, loss, "none", 0, d, scale))
        for method in ("top_outputs", "random_sampling", "random_projection"):
            for k in (1, 2, 5):
                if k >= d:
                    continue
                rows.append(_fit_eval(task, loss, method, k, d, scale))
        rows.append(_fit_eval(task, loss, "none", 0, d, scale,
                              strategy="one_vs_all"))
    return rows


def bench_fig1(scale) -> List[Dict]:
    """Training time of 100 trees vs output dimension (no early stopping)."""
    rows = []
    for d in (5, 10, 25, 50, 100):
        for method, k, strat in (("none", 0, "single_tree"),
                                 ("random_projection", 5, "single_tree"),
                                 ("none", 0, "one_vs_all")):
            if strat == "one_vs_all" and d > 25:
                continue                      # d trees/round: too slow on CPU
            sc = dict(scale, trees=min(scale["trees"], 40), es=0)
            rows.append(_fit_eval("multiclass", "multiclass", method, k, d,
                                  sc, strategy=strat))
            print(f"  fig1 d={d} {strat}/{method} "
                  f"{rows[-1]['time_s']}s", flush=True)
    return rows


def bench_fig3(scale) -> List[Dict]:
    """Learning curves: valid loss per round, Full vs Random Sampling k=2."""
    from repro.core.boosting import SketchBoost
    from repro.data.pipeline import make_tabular, train_test_split
    out = []
    X, y = make_tabular("multiclass", scale["n"], scale["m"], 9, seed=1)
    Xtr, Xte, ytr, yte = train_test_split(X, y, seed=1)
    for method, k in (("none", 0), ("random_sampling", 2),
                      ("random_projection", 2)):
        cfg = _cfg("multiclass", method, k, dict(scale, es=0))
        m = SketchBoost(cfg).fit(Xtr, ytr, eval_set=(Xte, yte))
        curve = [r.get("valid_loss") for r in m.history
                 if "valid_loss" in r]
        out.append({"method": method, "k": k, "curve": curve})
    return out


def bench_rounds(scale) -> List[Dict]:
    rows = []
    for task, loss, d in TASKS[:1]:
        for method, k in (("none", 0), ("top_outputs", 2),
                          ("random_sampling", 2), ("random_projection", 2)):
            r = _fit_eval(task, loss, method, k, d, scale)
            rows.append({"method": method, "k": k, "rounds": r["rounds"],
                         "test_loss": r["test_loss"]})
    return rows


HIST_QUICK = dict(n=24000, m=20, d=16, bins=64)
HIST_FULL = dict(n=120000, m=40, d=18, bins=256)
HIST_SMOKE = dict(n=2000, m=8, d=6, bins=32)


def bench_hist(scale) -> List[Dict]:
    """Histogram-engine microbench: per-level split-search cost of
    ``direct`` (full rebuild over all nodes) vs ``partition`` (node-sorted
    row tiles, O(n*m*c) per level) vs ``subtract`` (partition + sibling
    subtraction, ~half the scatter work) across sketch widths and depths.

    Times one whole `tree.grow_tree` per engine (warm, best of 3) — split
    scan and routing are identical across engines, so the delta isolates
    the histogram builder — and derives the per-level mean.  The acceptance
    guards run inline: all three engines must pick identical (feat, thr)
    per node on the bench seed, and the deepest level's subtraction
    histograms must match the direct build within the documented fp32
    tolerance.  `BENCH_hist.json` at the repo root is the standing
    baseline: diff ``time_s`` / ``per_level_ms`` across PRs.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import histogram as H
    from repro.core import tree as T
    from repro.core.histogram import resolve_kernel_mode

    sc = (HIST_FULL if scale is FULL else
          HIST_SMOKE if scale is SMOKE else HIST_QUICK)
    mode = resolve_kernel_mode(True)
    n, m, d, bins = sc["n"], sc["m"], sc["d"], sc["bins"]
    rng = np.random.default_rng(0)
    codes = jnp.asarray(rng.integers(0, bins, (n, m)).astype(np.uint8))
    G_full = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    Hd = jnp.ones((n, d), jnp.float32)
    ones = jnp.ones((n, 1), jnp.float32)

    rows: List[Dict] = []
    for k_label, k in ((2, 2), (5, 5), ("full", d)):
        stats = jnp.concatenate([G_full[:, :k], ones], axis=1)
        for depth in (3, 6):
            grown = {}
            for engine in ("direct", "partition", "subtract"):
                def fit():
                    tr, _ = T.grow_tree(codes, stats, G_full, Hd,
                                        depth=depth, n_bins=bins, lam=1.0,
                                        use_kernel=mode, hist_engine=engine)
                    return tr
                t0 = time.perf_counter()
                tree = fit()
                jax.block_until_ready(tree.value)
                cold = time.perf_counter() - t0
                warm = np.inf               # best-of-3: robust to CPU noise
                for _ in range(3):
                    t0 = time.perf_counter()
                    tree = fit()
                    jax.block_until_ready(tree.value)
                    warm = min(warm, time.perf_counter() - t0)
                grown[engine] = tree
                rows.append({
                    "sketch_k": k_label, "depth": depth, "engine": engine,
                    "n": n, "m": m, "bins": bins,
                    "cold_time_s": round(cold, 4),
                    "time_s": round(warm, 4),
                    "per_level_ms": round(warm / depth * 1e3, 2),
                })
                print(f"  hist k={k_label} depth={depth} {engine}: "
                      f"{warm:.4f}s ({rows[-1]['per_level_ms']}ms/level)",
                      flush=True)
            # Acceptance guards: identical split decisions across engines...
            for engine in ("partition", "subtract"):
                assert np.array_equal(np.asarray(grown["direct"].feat),
                                      np.asarray(grown[engine].feat)), engine
                assert np.array_equal(np.asarray(grown["direct"].thr),
                                      np.asarray(grown[engine].thr)), engine
            # ...and bounded subtraction drift on the deepest level's
            # histograms (replayed through the jnp builders).
            if depth == 6:
                state = H.init_level_state(n)
                node_pos = jnp.zeros((n,), jnp.int32)
                tree = grown["direct"]
                for lvl in range(depth - 1):
                    off = 2 ** lvl - 1
                    nn = 2 ** lvl
                    bits = T.route_bits(codes, node_pos,
                                        tree.feat[off:off + nn],
                                        tree.thr[off:off + nn])
                    node_pos = node_pos * 2 + bits
                    state = H.advance_level_state(state, bits)
                nn = 2 ** (depth - 1)
                direct = H.build_histograms_jnp(codes, node_pos, stats,
                                                n_nodes=nn, n_bins=bins)
                prev = H.build_histograms_jnp(codes, node_pos // 2, stats,
                                              n_nodes=nn // 2, n_bins=bins)
                sub = H.build_level_jnp(codes, stats, state, prev,
                                        n_nodes=nn, n_bins=bins,
                                        subtract=True)
                drift = float(jnp.max(jnp.abs(sub - direct)))
                scale_ref = float(jnp.max(jnp.abs(direct)))
                assert drift <= max(1e-3 * scale_ref, 1e-3), (drift,
                                                              scale_ref)
                rows[-1]["subtract_max_drift"] = drift

    payload = {
        "bench": "hist_engine",
        "backend": jax.default_backend(),
        "kernel_mode": mode,
        "scale": sc,
        "unix_time": int(time.time()),
        "rows": rows,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_hist.json"), "w") as f:
        json.dump(payload, f, indent=1, default=float)
    print(f"[bench:hist] wrote {os.path.join(root, 'BENCH_hist.json')}",
          flush=True)
    return rows


GBDT_QUICK = dict(n=4000, m=20, d=6, trees=40, depth=5, bins=64)
GBDT_FULL = dict(n=40000, m=60, d=16, trees=200, depth=6, bins=256)
GBDT_SMOKE = dict(n=800, m=10, d=4, trees=8, depth=4, bins=32)


def bench_gbdt(scale) -> List[Dict]:
    """Compiled-loop trajectory: rounds/sec and end-to-end fit time over
    {sketch_k in {2, 5, full}} x {single_tree, one_vs_all} x {scan, python},
    plus a growth-strategy axis (leaf-wise best-first vs level-wise at
    EQUAL leaf budgets).

    This is the repo's standing perf baseline: every PR can diff
    `BENCH_gbdt.json` (written to the repo root) to see whether the hot path
    moved.  `rounds_per_sec` counts boosting rounds (one multivariate tree —
    or d univariate trees for one_vs_all — per round); `trajectory` samples
    the cumulative train time every 10 rounds from the fit history.  The
    growth pairs carry an inline acceptance guard: best-first expansion of
    the same number of leaves (under a deeper depth bound) must reach
    strictly lower train loss than a full level-wise tree.
    """
    import jax
    from repro.core.boosting import GBDTConfig, SketchBoost
    from repro.core.histogram import resolve_kernel_mode
    from repro.data.pipeline import make_tabular, train_test_split

    sc = (GBDT_FULL if scale is FULL else
          GBDT_SMOKE if scale is SMOKE else GBDT_QUICK)
    X, y = make_tabular("multiclass", sc["n"], sc["m"], sc["d"], seed=0)
    Xtr, Xte, ytr, yte = train_test_split(X, y, seed=0)

    rows: List[Dict] = []

    def run_one(strategy, k_label, method, k, loop, depth, engine,
                growth="levelwise", max_leaves=0):
        cfg = GBDTConfig(loss="multiclass", strategy=strategy,
                         sketch_method=method, sketch_k=k,
                         n_trees=sc["trees"], depth=depth,
                         growth=growth, max_leaves=max_leaves,
                         n_bins=sc["bins"], learning_rate=0.1,
                         loop=loop, hist_engine=engine, seed=0)
        t0 = time.perf_counter()
        SketchBoost(cfg).fit(Xtr, ytr)           # cold: includes tracing
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        model = SketchBoost(cfg).fit(Xtr, ytr)   # warm: jit cache hit
        jax.block_until_ready(model.forest.value)
        dt = time.perf_counter() - t0
        traj = [round(r["train_time_s"], 3)
                for r in model.history if r["round"] % 10 == 0]
        rows.append({
            "strategy": strategy, "sketch_k": k_label,
            "method": method, "loop": loop, "depth": depth,
            "growth": growth, "max_leaves": max_leaves,
            "hist_engine": model.cfg.hist_engine,
            "rounds": int(model.forest.n_trees),
            "cold_fit_time_s": round(cold, 3),
            "fit_time_s": round(dt, 3),
            "rounds_per_sec": round(model.forest.n_trees / dt, 3),
            "train_loss": round(model.eval_loss(Xtr, ytr), 5),
            "test_loss": round(model.eval_loss(Xte, yte), 5),
            "trajectory_s": traj,
        })
        print(f"  gbdt {strategy} k={k_label} {loop} depth={depth} "
              f"{growth} {rows[-1]['hist_engine']}: "
              f"{rows[-1]['rounds_per_sec']} rounds/s "
              f"({rows[-1]['fit_time_s']}s)", flush=True)
        return rows[-1]

    for strategy in ("single_tree", "one_vs_all"):
        for k_label, method, k in ((2, "random_projection", 2),
                                   (5, "random_projection", 5),
                                   ("full", "none", 0)):
            for loop in ("scan", "python"):
                run_one(strategy, k_label, method, k, loop, sc["depth"],
                        "auto")
    # Engine comparison rows at depth 6 — where the direct builder's
    # O(n*m*c*2^l) per-level blow-up is largest; diff these pairs to see
    # the node-partitioned + sibling-subtraction win end to end.
    if scale is not SMOKE:
        for strategy, k_label, method, k in (
                ("single_tree", 5, "random_projection", 5),
                ("one_vs_all", "full", "none", 0)):
            for engine in ("auto", "direct"):
                run_one(strategy, k_label, method, k, "scan", 6, engine)
    # Growth-strategy axis: the same leaf budget (2^(depth-1) leaves per
    # tree) spent level-wise (full depth-1 tree) vs best-first under the
    # full depth bound, across sketch widths.
    budget = 2 ** (sc["depth"] - 1)
    for k_label, method, k in ((2, "random_projection", 2),
                               (5, "random_projection", 5),
                               ("full", "none", 0)):
        lvl = run_one("single_tree", k_label, method, k, "scan",
                      sc["depth"] - 1, "auto")
        lw = run_one("single_tree", k_label, method, k, "scan",
                     sc["depth"], "auto", growth="leafwise",
                     max_leaves=budget)
        # Acceptance guard: equal leaf budget, strictly better train fit
        # at bench scales.  Greedy best-first is not *mathematically*
        # guaranteed to win, so the tiny CI smoke shapes only require
        # no-worse (a knife-edge tie there must not fail unrelated PRs).
        if scale is SMOKE:
            assert lw["train_loss"] <= lvl["train_loss"] + 1e-6, (k_label,
                                                                  lw, lvl)
        else:
            assert lw["train_loss"] < lvl["train_loss"], (k_label, lw, lvl)

    payload = {
        "bench": "gbdt_compiled_loop",
        "backend": jax.default_backend(),
        "kernel_mode": resolve_kernel_mode(True),
        "scale": sc,
        "unix_time": int(time.time()),
        "rows": rows,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_gbdt.json"), "w") as f:
        json.dump(payload, f, indent=1, default=float)
    print(f"[bench:gbdt] wrote {os.path.join(root, 'BENCH_gbdt.json')}",
          flush=True)
    return rows


def bench_gbdt_dist(scale) -> List[Dict]:
    """Device-count scaling of the distributed grower (emulated hosts).

    Matrix: devices in {1, 2, 4, 8} (8 splits 4x2 over (data, model), the
    rest shard rows only) x sketch_k in {2, 5, full} x histogram-collective
    compression {off, on}.  Each cell records warm per-round wall-clock and
    the analytic collective payload (`distributed.round_collective_bytes`).
    Run via ``python -m benchmarks.run gbdt --dist`` — the ``--dist`` flag
    forces ``--xla_force_host_platform_device_count=8`` before jax loads.

    Inline acceptance guard: the compressed collective must move at most
    ``(k + 1) / (d + 1)`` of the uncompressed payload — the paper's
    communication claim restated for the histogram psum.

    Results are merged into ``BENCH_gbdt.json`` under ``dist_rows``,
    preserving any single-host ``rows`` already there.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import distributed as GD
    from repro.core import quantize as Q
    from repro.core.boosting import GBDTConfig
    from repro.core.losses import get_loss
    from repro.data.pipeline import make_tabular

    sc = (GBDT_FULL if scale is FULL else
          GBDT_SMOKE if scale is SMOKE else GBDT_QUICK)
    trees = min(sc["trees"], 16)            # the axis of interest is devices
    d = sc["d"]
    X, y = make_tabular("multiclass", sc["n"], sc["m"], d, seed=0)
    q = Q.fit_quantizer(X, sc["bins"])
    codes = Q.apply_quantizer(q, jnp.asarray(X))
    Y = jnp.asarray(y)

    n_dev = jax.device_count()
    rows: List[Dict] = []
    for dev in (1, 2, 4, 8):
        if dev > n_dev or sc["n"] % dev:
            print(f"  gbdt-dist skip devices={dev} "
                  f"(have {n_dev}, n={sc['n']})", flush=True)
            continue
        from repro.launch.mesh import device_subset_mesh
        mp = 2 if dev == 8 else 1           # 8 devices: exercise (4, 2)
        shape = (dev // mp, mp)
        mesh = device_subset_mesh(dev, mp)
        for k_label, method, k in ((2, "random_projection", 2),
                                   (5, "random_projection", 5),
                                   ("full", "none", 0)):
            for comp in ("none", "sketch"):
                cfg = GBDTConfig(
                    loss="multiclass", n_outputs=d, sketch_method=method,
                    sketch_k=k, n_trees=trees, depth=sc["depth"],
                    n_bins=sc["bins"], learning_rate=0.1, seed=0,
                    use_kernel=False, dist_hist_compression=comp,
                    dist_hist_k=0 if (comp == "none" or 0 < k < d)
                    else max(d - 2, 1))
                F, _, _ = GD.fit_distributed(cfg, mesh, codes, Y)  # cold
                t0 = time.perf_counter()
                F, _, _ = GD.fit_distributed(cfg, mesh, codes, Y)  # warm
                jax.block_until_ready(F)
                dt = time.perf_counter() - t0
                col = GD.round_collective_bytes(cfg, sc["m"], d)
                if comp == "sketch":
                    k_eff = cfg.dist_hist_k_effective
                    budget = (k_eff + 1) / (d + 1) * col["full_bytes"]
                    assert col["moved_bytes"] <= budget * (1 + 1e-6), (
                        "compressed collective exceeds the (k+1)/(d+1) "
                        "byte budget", cfg, col)
                rows.append({
                    "devices": dev, "mesh": "x".join(map(str, shape)),
                    "sketch_k": k_label, "dist_hist_compression": comp,
                    "dist_hist_k": cfg.dist_hist_k_effective
                    if comp == "sketch" else 0,
                    "rounds": trees,
                    "fit_time_s": round(dt, 3),
                    "round_time_s": round(dt / trees, 5),
                    "rounds_per_sec": round(trees / dt, 3),
                    "train_loss": round(
                        float(get_loss("multiclass").value(F, Y)), 5),
                    "collective": col,
                })
                print(f"  gbdt-dist devices={dev} k={k_label} comp={comp}: "
                      f"{rows[-1]['rounds_per_sec']} rounds/s "
                      f"moved={col['moved_bytes']}B", flush=True)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "BENCH_gbdt.json")
    payload = {}
    if os.path.exists(path):
        with open(path) as f:
            payload = json.load(f)
    payload.setdefault("bench", "gbdt_compiled_loop")
    payload["dist_backend"] = jax.default_backend()
    payload["dist_scale"] = dict(sc, trees=trees)
    payload["dist_unix_time"] = int(time.time())
    payload["dist_rows"] = rows
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    print(f"[bench:gbdt-dist] wrote {path}", flush=True)
    return rows


PRED_QUICK = dict(n=4000, m=20, d=6, trees=40, depth=5, bins=64, n_pred=20000)
PRED_FULL = dict(n=40000, m=60, d=16, trees=200, depth=6, bins=256,
                 n_pred=100000)
PRED_SMOKE = dict(n=600, m=10, d=4, trees=10, depth=4, bins=32, n_pred=2000)


def bench_predict(scale) -> List[Dict]:
    """Inference baseline: compiled packed-forest predict vs legacy paths.

    For models trained at ``sketch_k in {2, 5, full}`` (the forest shape is
    identical — k only changes which trees get grown), times three ways of
    scoring ``n_pred`` rows:

      * ``packed_chunked``   — `forest.predict_raw` on the `PackedForest`
                               (kernel-mode dispatched, chunk-streamed);
      * ``forest_scan``      — `tree.predict_forest`, the stacked-buffer scan
                               retained as the parity reference;
      * ``python_per_tree``  — one `tree.predict_tree` dispatch per tree,
                               the seed repo's uncompiled serving shape.

    `BENCH_predict.json` at the repo root is the standing baseline: diff
    ``rows_per_sec`` (warm, 2nd call) across PRs.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import forest as FO
    from repro.core import tree as T
    from repro.core.boosting import GBDTConfig, SketchBoost
    from repro.core.histogram import resolve_kernel_mode
    from repro.data.pipeline import make_tabular

    sc = (PRED_FULL if scale is FULL else
          PRED_SMOKE if scale is SMOKE else PRED_QUICK)
    mode = resolve_kernel_mode(True)
    X, y = make_tabular("multiclass", sc["n"], sc["m"], sc["d"], seed=0)
    rng = np.random.default_rng(1)
    X_pred = X[rng.integers(0, sc["n"], size=sc["n_pred"])]

    rows: List[Dict] = []
    for k_label, method, k in ((2, "random_projection", 2),
                               (5, "random_projection", 5),
                               ("full", "none", 0)):
        cfg = GBDTConfig(loss="multiclass", sketch_method=method, sketch_k=k,
                         n_trees=sc["trees"], depth=sc["depth"],
                         n_bins=sc["bins"], learning_rate=0.1, seed=0)
        model = SketchBoost(cfg).fit(X, y)
        codes = model._bin(X_pred)
        pf, forest = model.packed, model.forest
        chunk = min(4000, sc["n_pred"])    # even divisor: no tail padding

        def packed_chunked():
            return FO.predict_raw(pf, codes, mode=mode, row_chunk=chunk)

        def forest_scan():
            return T.predict_forest(forest, codes, cfg.learning_rate,
                                    model.base_score)

        def python_per_tree():
            acc = jnp.broadcast_to(model.base_score,
                                   (codes.shape[0], sc["d"]))
            for i in range(forest.n_trees):
                tr = T.Tree(feat=forest.feat[i], thr=forest.thr[i],
                            value=forest.value[i], gain=forest.feat[i])
                acc = acc + cfg.learning_rate * T.predict_tree(tr, codes)
            return acc

        for name, fn in (("packed_chunked", packed_chunked),
                         ("forest_scan", forest_scan),
                         ("python_per_tree", python_per_tree)):
            t0 = time.perf_counter()
            out = jax.block_until_ready(fn())
            cold = time.perf_counter() - t0
            warm = np.inf                   # best-of-3: robust to CPU noise
            for _ in range(3):
                t0 = time.perf_counter()
                out = jax.block_until_ready(fn())
                warm = min(warm, time.perf_counter() - t0)
            rows.append({
                "sketch_k": k_label, "path": name,
                "n_pred": sc["n_pred"], "trees": int(forest.n_trees),
                "depth": sc["depth"], "d": sc["d"],
                "cold_time_s": round(cold, 4), "warm_time_s": round(warm, 4),
                "rows_per_sec": round(sc["n_pred"] / warm),
                "checksum": round(float(jnp.sum(out)), 2),
            })
            print(f"  predict k={k_label} {name}: "
                  f"{rows[-1]['rows_per_sec']:,} rows/s "
                  f"(warm {warm:.3f}s)", flush=True)

    payload = {
        "bench": "forest_predict",
        "backend": jax.default_backend(),
        "kernel_mode": mode,
        "scale": sc,
        "unix_time": int(time.time()),
        "rows": rows,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_predict.json"), "w") as f:
        json.dump(payload, f, indent=1, default=float)
    print(f"[bench:predict] wrote {os.path.join(root, 'BENCH_predict.json')}",
          flush=True)
    return rows


SHAP_QUICK = dict(n=3000, m=16, d=6, trees=30, depth=4, bins=32, n_expl=512)
SHAP_FULL = dict(n=20000, m=40, d=16, trees=100, depth=6, bins=256,
                 n_expl=4096)
SHAP_SMOKE = dict(n=500, m=8, d=4, trees=8, depth=3, bins=16, n_expl=128)


def bench_shap(scale) -> List[Dict]:
    """Explanation-serving baseline: packed path-walk TreeSHAP vs the
    per-tree python walk.

    For models trained at ``sketch_k in {2, 5, full}``, times SHAP values
    for ``n_expl`` rows two ways:

      * ``packed_kernel``    — `explain.shap_values` (kernel-mode dispatched
                               vectorized path walk over the whole forest:
                               Pallas on TPU, the jnp oracle elsewhere);
      * ``python_per_tree``  — one `ref.tree_shap_ref` dispatch per tree,
                               the uncompiled per-tree loop a naive port
                               would run.

    Every row also records the local-accuracy residual
    ``max |base + phi.sum(features) - predict_raw|`` — a bench that stops
    being exact fails loudly.  `BENCH_shap.json` at the repo root is the
    standing baseline: diff ``rows_per_sec`` across PRs.
    """
    import jax
    import jax.numpy as jnp
    from repro import explain as EX
    from repro.core import forest as FO
    from repro.core.boosting import GBDTConfig, SketchBoost
    from repro.core.histogram import resolve_kernel_mode
    from repro.data.pipeline import make_tabular
    from repro.kernels import ref

    sc = (SHAP_FULL if scale is FULL else
          SHAP_SMOKE if scale is SMOKE else SHAP_QUICK)
    mode = resolve_kernel_mode(True)
    X, y = make_tabular("multiclass", sc["n"], sc["m"], sc["d"], seed=0)
    rng = np.random.default_rng(1)
    X_expl = X[rng.integers(0, sc["n"], size=sc["n_expl"])]

    rows: List[Dict] = []
    for k_label, method, k in ((2, "random_projection", 2),
                               (5, "random_projection", 5),
                               ("full", "none", 0)):
        cfg = GBDTConfig(loss="multiclass", sketch_method=method, sketch_k=k,
                         n_trees=sc["trees"], depth=sc["depth"],
                         n_bins=sc["bins"], learning_rate=0.1, seed=0)
        model = SketchBoost(cfg).fit(X, y)
        codes = model._bin(X_expl)
        pf = model.packed
        pack = EX.build_path_pack(pf)
        raw = np.asarray(FO.predict_raw(pf, codes, mode="jnp"))

        def packed_kernel():
            return EX.shap_values(pf, codes, mode=mode, pack=pack)

        def python_per_tree():
            n = codes.shape[0]
            phi = jnp.zeros((n, sc["m"], sc["d"]), jnp.float32)
            for i in range(pf.n_trees):
                phi = ref.tree_shap_ref(
                    phi, codes, pack.slot_feat[i:i + 1],
                    pack.slot_lo[i:i + 1], pack.slot_hi[i:i + 1],
                    pack.slot_z[i:i + 1], pack.leaf[i:i + 1],
                    pf.out_col[i:i + 1], pf.lr, depth=pf.depth)
            return phi, EX.expected_values(pf, pack)

        for name, fn in (("packed_kernel", packed_kernel),
                         ("python_per_tree", python_per_tree)):
            t0 = time.perf_counter()
            phi, base = fn()
            phi = jax.block_until_ready(phi)
            cold = time.perf_counter() - t0
            warm = np.inf                   # best-of-3: robust to CPU noise
            for _ in range(3):
                t0 = time.perf_counter()
                phi, base = fn()
                phi = jax.block_until_ready(phi)
                warm = min(warm, time.perf_counter() - t0)
            acc_err = float(np.max(np.abs(
                np.asarray(base) + np.asarray(phi).sum(axis=1) - raw)))
            assert acc_err < 1e-4, f"local accuracy broke: {acc_err}"
            rows.append({
                "sketch_k": k_label, "path": name,
                "n_expl": sc["n_expl"], "trees": int(pf.n_trees),
                "depth": sc["depth"], "d": sc["d"], "m": sc["m"],
                "cold_time_s": round(cold, 4), "warm_time_s": round(warm, 4),
                "rows_per_sec": round(sc["n_expl"] / warm),
                "local_acc_err": acc_err,
            })
            print(f"  shap k={k_label} {name}: "
                  f"{rows[-1]['rows_per_sec']:,} rows/s "
                  f"(warm {warm:.3f}s, |err| {acc_err:.1e})", flush=True)

    payload = {
        "bench": "forest_shap",
        "backend": jax.default_backend(),
        "kernel_mode": mode,
        "scale": sc,
        "unix_time": int(time.time()),
        "rows": rows,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_shap.json"), "w") as f:
        json.dump(payload, f, indent=1, default=float)
    print(f"[bench:shap] wrote {os.path.join(root, 'BENCH_shap.json')}",
          flush=True)
    return rows


def bench_kernels() -> List[Dict]:
    """Pallas (interpret) vs jnp oracle — correctness + structural cost.
    Wall-clock on CPU interpret mode is NOT the TPU number; report analytic
    FLOPs/bytes per call alongside."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    rows = []
    n, m, B, nodes, c = 4096, 16, 256, 8, 6
    ks = jax.random.split(jax.random.key(0), 3)
    codes = jax.random.randint(ks[0], (n, m), 0, B, jnp.int32)
    node = jax.random.randint(ks[1], (n,), 0, nodes, jnp.int32)
    stats = jax.random.normal(ks[2], (n, c), jnp.float32)

    def timeit(f, *a, reps=3):
        f(*a)                                        # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            jax.block_until_ready(f(*a))
        return (time.perf_counter() - t0) / reps * 1e6

    t_ref = timeit(lambda: ref.histogram_ref(codes, node, stats,
                                             n_nodes=nodes, n_bins=B))
    rows.append({"kernel": "histogram", "impl": "jnp_oracle",
                 "us_per_call": round(t_ref),
                 "analytic_flops": 2 * n * m * c})
    t_k = timeit(lambda: ops.histogram(codes, node, stats, n_nodes=nodes,
                                       n_bins=B, interpret=True))
    rows.append({"kernel": "histogram", "impl": "pallas_interpret",
                 "us_per_call": round(t_k),
                 "analytic_flops": 2 * n * m * c})

    b, hq, hkv, s, dh = 1, 8, 2, 1024, 64
    q = jax.random.normal(ks[0], (b, hq, s, dh), jnp.float32)
    kk = jax.random.normal(ks[1], (b, hkv, s, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, dh), jnp.float32)
    rows.append({"kernel": "flash_attention", "impl": "jnp_oracle",
                 "us_per_call": round(timeit(
                     lambda: ref.mha_ref(q, kk, v, causal=True))),
                 "analytic_flops": 4 * b * hq * s * s * dh // 2})
    rows.append({"kernel": "flash_attention", "impl": "pallas_interpret",
                 "us_per_call": round(timeit(
                     lambda: ops.flash_attention(q, kk, v, causal=True,
                                                 interpret=True))),
                 "analytic_flops": 4 * b * hq * s * s * dh // 2})
    return rows


def bench_compression() -> List[Dict]:
    """Sketched vs exact cross-pod all-reduce: bytes ratio + reconstruction."""
    import jax
    import jax.numpy as jnp
    from repro.distributed import compression as C
    rng = np.random.default_rng(0)
    grads = {"wq": jnp.asarray(rng.normal(size=(512, 256)).astype(np.float32)),
             "wo": jnp.asarray(rng.normal(size=(256, 512)).astype(np.float32)),
             "ln": jnp.asarray(rng.normal(size=(256,)).astype(np.float32))}
    rows = []
    for k in (8, 32, 128):
        ratio = C.compression_ratio(grads, k)
        sk, Pi, shape = C.compress_block(grads["wq"], jax.random.key(0), k)
        rec = C.decompress_block(sk, Pi, shape)
        rel = float(jnp.linalg.norm(rec - grads["wq"])
                    / jnp.linalg.norm(grads["wq"]))
        rows.append({"k": k, "bytes_ratio": round(ratio, 4),
                     "recon_rel_err": round(rel, 4)})
    return rows


SERVE_QUICK = dict(n=6000, m=24, d=6, trees=60, depth=6, bins=64,
                   n_bulk=20000, interactive=(1, 8, 64), n_requests=48,
                   prune_pct=60)
SERVE_FULL = dict(n=30000, m=48, d=10, trees=200, depth=6, bins=256,
                  n_bulk=100000, interactive=(1, 8, 64, 512), n_requests=96,
                  prune_pct=60)
SERVE_SMOKE = dict(n=800, m=10, d=4, trees=12, depth=4, bins=32,
                   n_bulk=4000, interactive=(1, 8, 32), n_requests=24,
                   prune_pct=50)


def bench_serve(scale) -> List[Dict]:
    """Serving-tier baseline: compression x quantization latency matrix.

    Trains ONE multiclass model, checkpoints it, then serves it through
    `training.serve_lib.ForestServer` at the four corners of the
    compression matrix — {fp32, int8-quantized} x {full, pruned+compacted}
    — over two request mixes:

      * ``interactive`` — cycling small batches of raw float features
        (padded-bucket path, includes binning): per-request p50/p99;
      * ``bulk``        — one large PRE-BINNED batch through
        ``predict_codes`` (the double-buffered chunk-stream path):
        best-of-3 warm rows/s.  Binning is identical across variants and
        would otherwise wash out the traversal differences the matrix
        exists to measure.

    The pruning threshold is picked adaptively (a percentile of the
    model's own positive split gains) so the pruned variants genuinely
    shrink.  `BENCH_serve.json` at the repo root is the standing baseline;
    the inline assert pins the tier's reason to exist: the
    quantized+pruned server must out-serve the fp32 full forest on bulk
    throughput.
    """
    import jax
    from repro.core import forest as FO
    from repro.core.boosting import SketchBoost
    from repro.core.histogram import resolve_kernel_mode
    from repro.data.pipeline import make_tabular
    from repro.io.checkpoint import save_forest_checkpoint
    from repro.training.serve_lib import ForestServer

    sc = (SERVE_FULL if scale is FULL else
          SERVE_SMOKE if scale is SMOKE else SERVE_QUICK)
    mode = resolve_kernel_mode(True)
    X, y = make_tabular("multiclass", sc["n"], sc["m"], sc["d"], seed=0)
    cfg = _cfg("multiclass", "random_projection", 2,
               dict(trees=sc["trees"], depth=sc["depth"], es=0),
               n_bins=sc["bins"])
    model = SketchBoost(cfg).fit(X, y)
    ckpt = os.path.join(RESULTS_DIR, "serve_bench_ckpt")
    save_forest_checkpoint(ckpt, model.packed, model.quantizer,
                           metadata={"loss": "multiclass"})

    # Adaptive pruning threshold: walk a percentile ladder of the model's
    # own positive split gains until the compacted depth genuinely shrinks
    # — the walk length is depth-bound, so a "pruned" variant that keeps
    # the full depth would measure nothing.
    gains = np.asarray(model.packed.gain)
    pos = gains[gains > 0]
    depth0 = int(model.packed.depth)
    for pct in (sc["prune_pct"], 70, 80, 90, 95, 99):
        alpha = float(np.percentile(pos, pct))
        d = int(FO.compact_forest(FO.prune_forest(model.packed,
                                                  alpha)).depth)
        if d < depth0:
            break
    print(f"  serve prune_alpha={alpha:.4g} (p{pct} of positive gains, "
          f"depth {depth0} -> {d})", flush=True)

    rng = np.random.default_rng(1)
    X_bulk = X[rng.integers(0, sc["n"], size=sc["n_bulk"])]
    codes_bulk = np.asarray(model._bin(X_bulk))    # binned once, untimed
    inter = [X[rng.integers(0, sc["n"], size=sc["interactive"][
        i % len(sc["interactive"])])]
        for i in range(sc["n_requests"])]

    variants = [
        ("fp32_full", {}),
        ("fp32_pruned", {"prune_alpha": alpha}),
        ("int8_full", {"quantize": "int8"}),
        ("int8_pruned", {"quantize": "int8", "prune_alpha": alpha}),
    ]
    rows: List[Dict] = []
    bulk_rate: Dict[str, float] = {}
    for name, over in variants:
        server = ForestServer.from_checkpoint(
            ckpt, max_batch=4096, row_chunk=min(4000, sc["n_bulk"]),
            double_buffer=True, **over)
        comp = server.compression

        # interactive mix: warm every bucket, then per-request latency
        for r in inter[:len(sc["interactive"])]:
            server.predict_raw(r)
        lat = []
        for r in inter:
            t0 = time.perf_counter()
            jax.block_until_ready(server.predict_raw(r))
            lat.append((time.perf_counter() - t0) * 1e3)
        lat = np.asarray(lat)

        # bulk mix: chunk-streamed double-buffered predict, best-of-3 warm
        server.predict_codes(codes_bulk)
        warm = np.inf
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(server.predict_codes(codes_bulk))
            warm = min(warm, time.perf_counter() - t0)
        bulk_rate[name] = sc["n_bulk"] / warm
        rows.append({
            "variant": name, "quantize": comp["quantize"],
            "prune_alpha": (round(comp["prune_alpha"], 6)
                            if comp["prune_alpha"] is not None else None),
            "nodes": comp["nodes_after"], "nodes_full": comp["nodes_before"],
            "depth": comp["depth_after"], "bytes": comp["bytes_after"],
            "bytes_full": comp["bytes_before"],
            "p50_ms": round(float(np.percentile(lat, 50)), 3),
            "p99_ms": round(float(np.percentile(lat, 99)), 3),
            "bulk_rows_per_sec": round(bulk_rate[name]),
            "bulk_warm_s": round(warm, 4),
        })
        print(f"  serve {name}: p50 {rows[-1]['p50_ms']:.2f}ms "
              f"p99 {rows[-1]['p99_ms']:.2f}ms  "
              f"bulk {rows[-1]['bulk_rows_per_sec']:,} rows/s  "
              f"({comp['nodes_after']}/{comp['nodes_before']} nodes, "
              f"{comp['bytes_after']:,} bytes)", flush=True)

    # The tier's reason to exist: compressed serving must beat the fp32
    # full forest on bulk throughput.
    assert bulk_rate["int8_pruned"] > bulk_rate["fp32_full"], (
        f"quantized+pruned serving ({bulk_rate['int8_pruned']:,.0f} rows/s) "
        f"does not beat the fp32 full forest "
        f"({bulk_rate['fp32_full']:,.0f} rows/s)")

    payload = {
        "bench": "forest_serve",
        "backend": jax.default_backend(),
        "kernel_mode": mode,
        "scale": sc,
        "prune_alpha": alpha,
        "speedup_int8_pruned_vs_fp32_full": round(
            bulk_rate["int8_pruned"] / bulk_rate["fp32_full"], 3),
        "unix_time": int(time.time()),
        "rows": rows,
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_serve.json"), "w") as f:
        json.dump(payload, f, indent=1, default=float)
    print(f"[bench:serve] wrote {os.path.join(root, 'BENCH_serve.json')}",
          flush=True)
    return rows


BENCHES = {
    "gbdt": lambda sc: bench_gbdt(sc),
    "hist": lambda sc: bench_hist(sc),
    "predict": lambda sc: bench_predict(sc),
    "serve": lambda sc: bench_serve(sc),
    "shap": lambda sc: bench_shap(sc),
    "table1": lambda sc: bench_table1(sc),
    "fig1": lambda sc: bench_fig1(sc),
    "fig3": lambda sc: bench_fig3(sc),
    "rounds": lambda sc: bench_rounds(sc),
    "kernels": lambda sc: bench_kernels(),
    "compression": lambda sc: bench_compression(),
}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("benches", nargs="*", default=[],
                    choices=list(BENCHES) + [[]],
                    help="subset to run (default: all)")
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-speed tiny shapes (predict/gbdt smokes)")
    ap.add_argument("--dist", action="store_true",
                    help="add the distributed device-count matrix to the "
                         "gbdt bench (emulates 8 CPU hosts; jax is imported "
                         "lazily so the flag can still take effect)")
    args = ap.parse_args()
    if args.dist:
        # Must land before the first jax import (all benches import lazily).
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    scale = FULL if args.full else SMOKE if args.smoke else QUICK
    names = args.benches or list(BENCHES)
    os.makedirs(RESULTS_DIR, exist_ok=True)

    for name in names:
        print(f"=== bench {name}", flush=True)
        t0 = time.perf_counter()
        rows = BENCHES[name](scale)
        if name == "gbdt" and args.dist:
            rows = rows + bench_gbdt_dist(scale)
        dt = time.perf_counter() - t0
        path = os.path.join(RESULTS_DIR, f"bench_{name}.json")
        with open(path, "w") as f:
            json.dump(rows, f, indent=1, default=float)
        # CSV summary
        if rows and isinstance(rows[0], dict):
            keys = [k for k in rows[0] if k not in ("curve", "trajectory_s")]
            print(",".join(keys))
            for r in rows:
                print(",".join(str(r.get(k, "")) for k in keys))
        print(f"[bench:{name}] {len(rows)} rows in {dt:.1f}s -> {path}",
              flush=True)


if __name__ == "__main__":
    main()
