#!/usr/bin/env python3
"""On-chip smoke test of SketchBoost's main path, in one process.

    python chip_smoke.py             # one TPU chip: train, predict, serve, explain
    python chip_smoke.py --chips 4   # four chips: fit_distributed vs one device

Runs the repo's shipped GBDT configuration
(`repro.configs.sketchboost_tabular`: m=100 features, d=512 classes,
random-projection sketch k=5, 256 bins, depth 6, level-wise, single_tree,
subtract engine, fp32) through the entry points a user calls, on data made
from a seed, and checks each phase's result against the plain jnp path on
the same chip.  Only the row count and the number of trees are cut.

Every phase prints one line of what it found; any failed check raises, so
the process exits non-zero.  The last line of standard output is a JSON
object ``{"ok": true, "device": {...}}``, printed only when every phase
passed.  The script refuses to run without a TPU and writes only under the
git-ignored ``smoke_out/`` (plus JAX's compile cache, see
`repro.runtime.compile_cache`).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))
OUT_DIR = os.path.join(HERE, "smoke_out")

# The configuration's 2,097,152 rows do not fit one 16 GB chip: the tiles
# histogram buffer of one boosting round grows with n (a boost_scan segment
# compiles to 6.5 GiB of temporaries for a v5e at 2^17 rows, ~13 GiB at
# 2^18).  2^17 is the largest power of two with room to spare.
N_TRAIN = 131_072
N_HELD_OUT = 16_384
N_TREES = 8
N_EXPLAIN = 64
REQUEST_ROWS = (1, 2, 7, 31, 64, 500, 1000, 4096)


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def max_abs(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def device_phase(chips: int) -> dict:
    import jax
    from repro.core.histogram import resolve_kernel_mode
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log("device", f"platform={dev['platform']} kind={dev['kind']} "
                  f"count={dev['count']}")
    if dev["platform"] != "tpu":
        raise SystemExit(f"no TPU: JAX found {dev['platform']!r} devices")
    check(dev["count"] >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {dev['count']}")
    check("REPRO_PALLAS_INTERPRET" not in os.environ,
          "REPRO_PALLAS_INTERPRET is set: kernels would run interpreted")
    mode = resolve_kernel_mode(True)
    check(mode == "pallas", f"use_kernel=True resolves to {mode!r}")
    log("device", f"use_kernel=True -> {mode}")
    return dev


def smoke_config(n_trees: int = N_TREES, **overrides):
    """The shipped configuration with its trees cut to ``n_trees``."""
    from repro.configs.sketchboost_tabular import CONFIG
    return dataclasses.replace(CONFIG, n_trees=n_trees, **overrides)


def make_data(n_train: int, n_held_out: int, m: int, d: int, seed: int = 0):
    from repro.data.pipeline import make_tabular
    X, y = make_tabular("multiclass", n_train + n_held_out, m, d, seed=seed)
    return X[:n_train], y[:n_train], X[n_train:], y[n_train:]


def count_kernels(model, X, Xv) -> int:
    """``tpu_custom_call`` ops in the compiled scan segment the fit ran."""
    import jax
    import jax.numpy as jnp
    from repro.core import boosting as B
    cfg = model.cfg.strip_io()
    n, m = X.shape
    nv, d = Xv.shape[0], cfg.n_outputs
    S = jax.ShapeDtypeStruct
    key = jax.eval_shape(lambda: jax.random.key(0))
    lowered = B.boost_scan.lower(
        S((n, d), jnp.float32), S((n, m), jnp.uint8), S((n,), jnp.int32),
        S((nv, d), jnp.float32), S((nv, m), jnp.uint8), S((nv,), jnp.int32),
        key, cfg=cfg, n_steps=min(cfg.scan_chunk, cfg.n_trees),
        has_eval=True)
    return lowered.compile().as_text().count("tpu_custom_call")


def train_loss(model, X, y, iteration=None) -> float:
    from repro.core import losses as L
    raw = model.predict_raw(X, iteration)
    return float(L.get_loss(model.cfg.loss).value(raw, model._targets(y, 0)))


def train_phase(cfg, X, y, Xv, yv):
    import jax
    import numpy as np
    from repro.configs.sketchboost_tabular import CONFIG, N_ROWS
    from repro.core.boosting import SketchBoost

    def fit(c):
        t0 = time.perf_counter()
        model = SketchBoost(c).fit(X, y, eval_set=(Xv, yv))
        jax.block_until_ready(model.packed.leaf)
        return model, time.perf_counter() - t0

    model, t_first = fit(cfg)
    _, t_warm = fit(cfg)
    log("train", f"n={X.shape[0]} m={X.shape[1]} d={cfg.n_outputs} "
                 f"trees={cfg.n_trees} (the configuration: {N_ROWS:,} rows, "
                 f"{CONFIG.n_trees} trees)")
    log("train", f"fit first={t_first:.2f}s warm={t_warm:.2f}s "
                 f"compile~={t_first - t_warm:.2f}s (smoke timing, not a "
                 "benchmark)")
    n_kernels = count_kernels(model, X, Xv)
    log("train", f"tpu_custom_call in compiled scan segment: {n_kernels}")
    check(n_kernels > 0, "the compiled round runs no Pallas kernel")

    first, last = (train_loss(model, X, y, 1), train_loss(model, X, y))
    vl = [h["valid_loss"] for h in model.history]
    log("train", f"train loss round1={first:.6f} round{cfg.n_trees}="
                 f"{last:.6f}; valid loss {vl[0]:.6f} -> {vl[-1]:.6f}")
    check(np.isfinite(last) and last < first, "train loss did not fall")
    check(vl[-1] < vl[0], "held-out loss did not fall")

    # Same seed through the jnp path on the chip.  The subtract engine may
    # legally flip near-tied splits, so losses and predictions are
    # compared, not tree arrays.
    model_j, _ = fit(dataclasses.replace(cfg, use_kernel=False))
    last_j = train_loss(model_j, X, y)
    raw_k, raw_j = model.predict_raw(Xv), model_j.predict_raw(Xv)
    same = float(np.mean((np.asarray(model.forest.feat)
                          == np.asarray(model_j.forest.feat))
                         & (np.asarray(model.forest.thr)
                            == np.asarray(model_j.forest.thr))))
    d_loss = abs(last - last_j)
    log("train", f"kernel vs jnp fit: train loss {last:.7f} vs {last_j:.7f} "
                 f"(|diff|={d_loss:.3e}), valid loss "
                 f"{model.history[-1]['valid_loss']:.7f} vs "
                 f"{model_j.history[-1]['valid_loss']:.7f}, max |raw diff|="
                 f"{max_abs(raw_k, raw_j):.3e}, identical splits "
                 f"{same:.4f}")
    # A flipped near-tie routes a few rows to another leaf of nearly the
    # same gain; it moves the mean loss by far less than one round does
    # (~1e-2 here), so 1e-4 of the loss separates that from a wrong kernel.
    check(d_loss <= 1e-4 * abs(last_j),
          f"kernel and jnp fits disagree on train loss by {d_loss:.3e}")
    return model


# Kernel vs jnp traversal on the same forest: every contraction in the
# traversal kernel is an exact 0/1 selection at HIGHEST precision and the
# leaves are scaled by lr before the gather on both paths, so the only
# rounding is the same per-tree add; allow one ulp of the largest score.
def ulp_bound(ref) -> float:
    import numpy as np
    return float(np.spacing(np.float32(np.max(np.abs(np.asarray(ref))))))


def predict_phase(model, Xv):
    import jax.numpy as jnp
    import numpy as np
    from repro.core import forest as FO
    from repro.training.serve_lib import ForestServeConfig, ForestServer
    codes_v = model._bin(Xv)
    raw_k = model.predict_raw(Xv)
    raw_j = FO.predict_raw(model.packed, codes_v, mode="jnp")
    err, tol = max_abs(raw_k, raw_j), ulp_bound(raw_j)
    log("predict", f"fp32 kernel vs jnp traversal: max |diff|={err:.3e} "
                   f"(bound {tol:.3e}), shape={tuple(raw_k.shape)}")
    check(raw_k.shape == raw_j.shape and err <= tol,
          "fp32 traversal kernel disagrees with the jnp walk")
    proba = np.asarray(model.predict(Xv))
    check(np.all(np.isfinite(proba))
          and np.allclose(proba.sum(1), 1.0, atol=1e-5),
          "predict() probabilities are not finite rows summing to 1")

    server = ForestServer(model.packed, model.quantizer,
                          ForestServeConfig(quantize="int8"))
    check(server.quantized == "int8", "server did not quantize to int8")
    raw_q = server.predict_raw(Xv)
    raw_qj = FO.predict_raw(server.packed, codes_v, mode="jnp",
                            row_chunk=server.cfg.max_batch)
    err_q, tol_q = max_abs(raw_q, raw_qj), ulp_bound(raw_qj)
    agree = float(jnp.mean(jnp.argmax(raw_q, 1) == jnp.argmax(raw_k, 1)))
    log("predict", f"int8 serving kernel vs jnp: max |diff|={err_q:.3e} "
                   f"(bound {tol_q:.3e}); int8 vs fp32 max |diff|="
                   f"{max_abs(raw_q, raw_k):.3e}, argmax agreement "
                   f"{agree:.4f}")
    check(err_q <= tol_q, "int8 traversal kernel disagrees with the jnp walk")


def serve_phase(model, Xv):
    import numpy as np
    from repro.io.checkpoint import save_forest_checkpoint
    from repro.training.serve_lib import ForestServer
    root = os.path.join(OUT_DIR, "forest_ckpt")
    shutil.rmtree(root, ignore_errors=True)
    save_forest_checkpoint(root, model.packed, model.quantizer,
                           metadata={"loss": model.cfg.loss})
    server = ForestServer.from_checkpoint(root)
    ofs, requests = 0, []
    for r in REQUEST_ROWS:
        requests.append(Xv[ofs:ofs + r])
        ofs += r
    want = np.asarray(model.predict(Xv[:ofs]))
    got = [server.serve([r])[0] for r in requests]        # one batch each
    got_all = server.serve(requests)                       # one micro-batch
    err = max(max_abs(np.concatenate(got), want),
              max_abs(np.concatenate(got_all), want))
    log("serve", f"checkpoint -> ForestServer: {len(requests)} requests "
                 f"of {list(REQUEST_ROWS)} rows, buckets "
                 f"{server.buckets.active_buckets}, max |diff| vs "
                 f"model.predict={err:.3e}")
    # Each row's walk is independent of its batch; only the softmax's
    # vectorized reduction may see another padded batch shape.
    check(err <= 1e-6, "served probabilities differ from model.predict")


def explain_phase(model, Xv):
    import numpy as np
    from repro import explain as EX
    X = Xv[:N_EXPLAIN]
    phi, base = model.shap_values(X)
    raw = np.asarray(model.predict_raw(X))
    acc_err = max_abs(np.asarray(base) + np.asarray(phi).sum(1), raw)
    acc_tol = 32 * ulp_bound(raw)
    phi_j, _ = EX.shap_values(model.packed, model._bin(X), mode="jnp")
    err = max_abs(phi, phi_j)
    log("explain", f"shap_values {tuple(phi.shape)}: local accuracy max "
                   f"|base + sum(phi) - raw|={acc_err:.3e} (bound "
                   f"{acc_tol:.3e}); kernel vs jnp oracle max |diff|="
                   f"{err:.3e}")
    # Local accuracy is exact in real arithmetic; in float32 the ~110 terms
    # (base, 100 features, 8 trees) are summed in another order than the
    # traversal's, a few ulps of max |raw| (3 ulps at these widths on the
    # CPU).  32 ulps leaves room for that and still catches one bf16 MXU
    # pass anywhere on the path (~300 ulps).  The oracle sums the leaf axis
    # in another order than the kernel's matmul.
    check(acc_err <= acc_tol, "SHAP local accuracy violated")
    check(err <= 1e-5, "SHAP kernel disagrees with the jnp oracle")


def shards(name: str, x) -> set:
    devs = set()
    for s in x.addressable_shards:
        devs.add(s.device.id)
        log("dist", f"{name} shard {tuple(s.data.shape)} on device "
                    f"{s.device.id}")
    return devs


def distributed_phase(cfg, X, y):
    """fit_distributed on a (4, 1) mesh vs the single-device loop."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import distributed as GD
    from repro.core import losses as L
    from repro.core import quantize as Q
    from repro.core.boosting import boost_step
    from repro.launch.mesh import device_subset_mesh

    cfg = dataclasses.replace(cfg, use_kernel=False)
    d = cfg.n_outputs
    q = Q.fit_quantizer(X, cfg.n_bins, seed=cfg.seed)
    codes = Q.apply_quantizer(q, jnp.asarray(X))
    Y = jnp.asarray(y, jnp.int32)
    mesh = device_subset_mesh(4, 1)
    rows = NamedSharding(mesh, P("data"))
    codes_d, Y_d = jax.device_put(codes, rows), jax.device_put(Y, rows)

    t0 = time.perf_counter()
    F_d, forest, history = GD.fit_distributed(cfg, mesh, codes_d, Y_d,
                                              eval_every=1)
    jax.block_until_ready(F_d)
    t_dist = time.perf_counter() - t0
    devs = shards("codes", codes_d) | shards("F", F_d)
    check(len(devs) == 4, f"sharded arrays sit on devices {sorted(devs)}")

    # The single-device reference: the key schedule fit_distributed
    # documents, on device 0.
    dev0 = jax.devices()[0]
    codes_1, Y_1 = jax.device_put(codes, dev0), jax.device_put(Y, dev0)
    F_s = jax.device_put(jnp.zeros((X.shape[0], d), jnp.float32), dev0)
    key = jax.random.key(cfg.seed)
    t0 = time.perf_counter()
    for _ in range(cfg.n_trees):
        key, sub = jax.random.split(key)
        F_s, _ = boost_step(F_s, codes_1, Y_1, sub, cfg)
    jax.block_until_ready(F_s)
    t_one = time.perf_counter() - t0
    loss = L.get_loss(cfg.loss)
    l_d, l_s = float(loss.value(F_d, Y)), float(loss.value(F_s, Y))
    err = max_abs(F_d, F_s)
    log("dist", f"mesh (4, 1) rows={X.shape[0]} d={d} trees={cfg.n_trees}: "
                f"train loss {history[0]['train_loss']:.6f} -> "
                f"{history[-1]['train_loss']:.6f}; vs one device loss "
                f"{l_d:.7f} vs {l_s:.7f}, max |F diff|={err:.3e}; "
                f"first-call seconds 4 chips={t_dist:.2f} 1 chip={t_one:.2f} "
                "(smoke timing, not a benchmark)")
    check(history[-1]["train_loss"] < history[0]["train_loss"],
          "distributed train loss did not fall")
    # docs/distributed.md: generic floats under random_projection keep the
    # split structure except exact gain ties, predictions allclose (the
    # parity suite's rtol 1e-5 / atol 2e-5); a tie flip moves the mean
    # loss by far less than 1e-4 of it.
    close = np.allclose(np.asarray(F_d), np.asarray(F_s), rtol=1e-5,
                        atol=2e-5)
    check(close or abs(l_d - l_s) <= 1e-4 * abs(l_s),
          "fit_distributed disagrees with the single-device fit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only fit_distributed on a (4, 1) mesh and "
                         "its single-device reference")
    args = ap.parse_args(argv)
    from repro.runtime.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    dev = device_phase(args.chips)
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log("device", f"compile cache: {cache} ({n_cached} entries at start)")
    os.makedirs(OUT_DIR, exist_ok=True)

    cfg = smoke_config()
    m, d = 100, cfg.n_outputs
    t0 = time.perf_counter()
    X, y, Xv, yv = make_data(N_TRAIN, N_HELD_OUT, m, d)
    log("data", f"make_tabular multiclass n={N_TRAIN}+{N_HELD_OUT} m={m} "
                f"d={d} seed=0 in {time.perf_counter() - t0:.2f}s")
    if args.chips == 4:
        distributed_phase(cfg, X, y)
    else:
        model = train_phase(cfg, X, y, Xv, yv)
        predict_phase(model, Xv)
        serve_phase(model, Xv)
        explain_phase(model, Xv)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
