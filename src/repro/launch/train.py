"""Training launcher: end-to-end driver for any assigned arch (or the GBDT
workload) with checkpoint/restart, straggler watchdog, and optional sketched
cross-pod gradient compression.

CPU-smoke scale by default (reduced config); pass --full-config only on real
hardware.  Examples:

  PYTHONPATH=src python -m repro.launch.train --arch gemma-7b --steps 20
  PYTHONPATH=src python -m repro.launch.train --arch sketchboost-gbdt \
      --rows 20000 --outputs 16 --trees 50
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config, smoke_config
from repro.data import pipeline as data
from repro.launch.mesh import host_device_mesh
from repro.models import lm
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault import RestartableLoop
from repro.training import optimizer as opt
from repro.training import train_lib


def train_lm(args) -> Dict[str, Any]:
    cfg = get_config(args.arch) if args.full_config else smoke_config(args.arch)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    if args.d_model:
        hd = max(32, args.d_model // cfg.n_heads)
        cfg = dataclasses.replace(cfg, d_model=args.d_model, head_dim=hd,
                                  d_ff=0 if cfg.d_ff == 0 else 4 * args.d_model)
    mesh = (host_device_mesh(model_parallel=args.model_parallel)
            if args.mesh else None)
    tcfg = train_lib.TrainConfig(
        opt=opt.OptConfig(name=args.optimizer, lr=args.lr,
                          warmup_steps=min(100, args.steps // 10 + 1),
                          decay_steps=args.steps),
        compress_pods=args.compress, compress_rank=args.compress_rank)
    step_fn = train_lib.jit_train_step(cfg, tcfg, mesh, donate=False)

    params = lm.init(cfg, jax.random.key(args.seed))
    opt_state = opt.opt_init(params, tcfg.opt)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params, "
          f"batch={args.batch} seq={args.seq}")

    batches = data.lm_batches(
        cfg.vocab_size, args.batch, args.seq, seed=args.seed,
        embed_dim=cfg.d_model if cfg.embed_inputs else 0,
        image_tokens=cfg.n_image_tokens if cfg.family == "vlm" else 0,
        d_model=cfg.d_model)

    def loop_step(state, batch):
        params, opt_state, step = state
        b = {k: jnp.asarray(v) for k, v in batch.items()}
        params, opt_state, metrics = step_fn(params, opt_state, b,
                                             jnp.int32(step))
        return (params, opt_state, step + 1), metrics

    loop = RestartableLoop(args.ckpt_dir, loop_step,
                           save_every=args.save_every) if args.ckpt_dir \
        else None
    logs = []

    def on_metrics(step, m):
        rec = {"step": step, "loss": float(m["loss"]),
               "grad_norm": float(m["grad_norm"]),
               "step_time_s": m["step_time_s"]}
        logs.append(rec)
        if step % args.log_every == 0:
            print(f"[train] step {step} loss={rec['loss']:.4f} "
                  f"gnorm={rec['grad_norm']:.2f} {rec['step_time_s']:.2f}s")

    state = (params, opt_state, 0)
    if loop is not None:
        state, _ = loop.run(state, batches, args.steps, on_metrics)
    else:
        for i, batch in enumerate(batches):
            if i >= args.steps:
                break
            t0 = time.perf_counter()
            state, metrics = loop_step(state, batch)
            on_metrics(i, {**metrics,
                           "step_time_s": time.perf_counter() - t0})
    final_loss = logs[-1]["loss"] if logs else float("nan")
    first_loss = logs[0]["loss"] if logs else float("nan")
    print(f"[train] done: loss {first_loss:.4f} -> {final_loss:.4f}")
    return {"first_loss": first_loss, "final_loss": final_loss, "logs": logs}


def train_gbdt(args) -> Dict[str, Any]:
    from repro.core.boosting import GBDTConfig, SketchBoost
    if args.dist:
        return train_gbdt_dist(args)
    X, y = data.make_tabular("multiclass", args.rows, args.features,
                             args.outputs, seed=args.seed)
    Xtr, Xte, ytr, yte = data.train_test_split(X, y, seed=args.seed)
    cfg = GBDTConfig(loss="multiclass", n_trees=args.trees, depth=6,
                     sketch_method=args.sketch, sketch_k=args.sketch_k,
                     learning_rate=args.lr if args.lr != 3e-4 else 0.1,
                     early_stopping_rounds=50,
                     guard_policy=args.guard_policy,
                     save_every=args.save_every if args.ckpt_dir else 0,
                     ckpt_dir=args.ckpt_dir,
                     resume_from=args.ckpt_dir if args.resume else "")
    t0 = time.perf_counter()
    model = SketchBoost(cfg).fit(Xtr, ytr, eval_set=(Xte, yte), verbose=True)
    dt = time.perf_counter() - t0
    loss = model.eval_loss(Xte, yte)
    import numpy as np
    acc = float((np.asarray(model.predict(Xte)).argmax(1) == yte).mean())
    print(f"[gbdt] {args.sketch} k={args.sketch_k}: loss={loss:.4f} "
          f"acc={acc:.4f} time={dt:.1f}s")
    return {"loss": loss, "acc": acc, "time_s": dt}


def train_gbdt_dist(args) -> Dict[str, Any]:
    """GBDT through `core.distributed` on a (data, model) device mesh.

    Shards rows over the data axis and outputs over the model axis; trees
    are bit-compatible with the single-device fit (see
    tests/test_distributed_parity.py).  On CPU, emulate hosts by exporting
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` BEFORE launching
    (this module imports jax at load time, so the env var cannot be set
    here).  ``--compress`` routes the histogram collective through the JL
    sketch (`--compress-rank` is the channel width).
    """
    import numpy as np
    from repro.core import distributed as GD
    from repro.core import forest as FO
    from repro.core import quantize as Q
    from repro.core.boosting import GBDTConfig
    from repro.launch.mesh import device_subset_mesh

    X, y = data.make_tabular("multiclass", args.rows, args.features,
                             args.outputs, seed=args.seed)
    Xtr, Xte, ytr, yte = data.train_test_split(X, y, seed=args.seed)
    n_dev = len(jax.devices())
    mp = args.model_parallel
    dp = max(n_dev // mp, 1)
    # fit_distributed shards rows over the data axis: trim the ragged tail.
    n_tr = (len(ytr) // dp) * dp
    Xtr, ytr = Xtr[:n_tr], ytr[:n_tr]
    mesh = device_subset_mesh(dp * mp, mp)
    cfg = GBDTConfig(
        loss="multiclass", n_outputs=args.outputs, n_trees=args.trees,
        depth=6, sketch_method=args.sketch, sketch_k=args.sketch_k,
        learning_rate=args.lr if args.lr != 3e-4 else 0.1, seed=args.seed,
        use_kernel=False,
        dist_hist_compression="sketch" if args.compress else "none",
        dist_hist_k=args.compress_rank if args.compress else 0,
        guard_policy=args.guard_policy,
        save_every=args.save_every if args.ckpt_dir else 0,
        ckpt_dir=args.ckpt_dir,
        resume_from=args.ckpt_dir if args.resume else "")
    q = Q.fit_quantizer(Xtr, cfg.n_bins)
    codes_tr = Q.apply_quantizer(q, jnp.asarray(Xtr))
    t0 = time.perf_counter()
    F, forest, history = GD.fit_distributed(cfg, mesh, codes_tr,
                                            jnp.asarray(ytr), eval_every=10)
    jax.block_until_ready(F)
    dt = time.perf_counter() - t0
    pf = FO.pack_forest(forest, jnp.zeros((args.outputs,), jnp.float32),
                        cfg.learning_rate, max_depth=cfg.depth)
    codes_te = Q.apply_quantizer(q, jnp.asarray(Xte))
    scores = np.asarray(FO.predict_raw(pf, codes_te))
    acc = float((scores.argmax(1) == yte).mean())
    bytes_model = GD.round_collective_bytes(cfg, args.features, args.outputs)
    print(f"[gbdt-dist] mesh={dp}x{mp} {args.sketch} k={args.sketch_k} "
          f"compress={cfg.dist_hist_compression}: acc={acc:.4f} "
          f"time={dt:.1f}s moved={bytes_model['moved_bytes']}B/round")
    return {"acc": acc, "time_s": dt, "mesh": f"{dp}x{mp}",
            "collective": bytes_model,
            "history": history}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True,
                    choices=ARCH_NAMES + ["sketchboost-gbdt"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor", "sgd"])
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0)
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--mesh", action="store_true",
                    help="shard over available devices")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--compress", action="store_true",
                    help="sketched cross-pod gradient all-reduce")
    ap.add_argument("--compress-rank", type=int, default=32)
    ap.add_argument("--ckpt-dir", default="",
                    help="checkpoint directory; for the GBDT this enables "
                         "resumable round-boundary (format-v4) checkpoints")
    ap.add_argument("--save-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true",
                    help="resume the GBDT fit from --ckpt-dir's latest "
                         "round checkpoint (bit-identical continuation)")
    ap.add_argument("--guard-policy", default="off",
                    choices=["off", "raise", "skip_round", "clip"],
                    help="non-finite gradient guard (docs/robustness.md)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    # gbdt
    ap.add_argument("--rows", type=int, default=20000)
    ap.add_argument("--features", type=int, default=50)
    ap.add_argument("--outputs", type=int, default=16)
    ap.add_argument("--trees", type=int, default=100)
    ap.add_argument("--sketch", default="random_projection",
                    choices=["none", "top_outputs", "random_sampling",
                             "random_projection", "truncated_svd"])
    ap.add_argument("--sketch-k", type=int, default=5)
    ap.add_argument("--dist", action="store_true",
                    help="train the GBDT through core.distributed on a "
                         "(data, model) mesh; --model-parallel sets the "
                         "model axis, --compress/--compress-rank the "
                         "histogram-collective compression.  To emulate "
                         "hosts on CPU, export XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=8 before launching")
    args = ap.parse_args()
    enable_compile_cache()

    res = (train_gbdt(args) if args.arch == "sketchboost-gbdt"
           else train_lm(args))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)


if __name__ == "__main__":
    main()
