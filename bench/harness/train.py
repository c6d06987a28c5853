"""Training cells: ``SketchBoost.fit`` on the cell's job, back to back.

Set-up makes the data from the seed, then runs one fit of the job, which
compiles (or loads from the persistent cache) every program the window
runs.  The window then starts fits until ``seconds`` have passed; a fit
started while it is open runs to its end and counts.  The rate is all
rounds of all those fits over the time from the opening of the window to
the end of the last fit, so it includes the per-fit host work a user pays
(quantile fit, binning, copies, packing).
"""
from __future__ import annotations

import gc
import time
from types import SimpleNamespace
from typing import Any, Dict

import numpy as np

from harness import common, data


def gbdt_config(cell: Dict[str, Any], seed31: int, **overrides):
    """The program's configuration for the cell's job."""
    from repro.core.boosting import GBDTConfig
    conf, job = cell["config"], cell["traffic"]
    kw = dict(conf["gbdt"])
    kw.update(loss=conf["task"], n_outputs=conf["n_outputs"],
              n_trees=job["n_trees"], seed=seed31)
    kw.update(overrides)
    return GBDTConfig(**kw)


def fit(cfg, X, y, Xv, yv):
    import jax
    from repro.core.boosting import SketchBoost
    model = SketchBoost(cfg).fit(X, y, eval_set=(Xv, yv))
    jax.block_until_ready(model.packed.leaf)
    return model


def split_rounds(job: Dict[str, Any], seed: int) -> list:
    """Round 0 and ``split_check_rounds - 1`` more drawn from the seed."""
    n, k = job["n_trees"], job["split_check_rounds"]
    rng = np.random.default_rng([seed, 1])
    rest = rng.choice(np.arange(1, n), size=min(k - 1, n - 1),
                      replace=False)
    return sorted({0, *map(int, rest)})


def answer(model) -> Dict[str, np.ndarray]:
    """What a fit produced, on the host: its trees and its eval losses."""
    f = model.forest
    return {"feat": np.asarray(f.feat), "thr": np.asarray(f.thr),
            "value": np.asarray(f.value), "gain": np.asarray(f.gain),
            "cover": np.asarray(f.cover),
            "vloss": np.array([h["valid_loss"] for h in model.history],
                              np.float64)}


def check(cell, ans, X, y, Xv, yv, seed31: int, seed: int) -> Dict:
    from configs import reference
    readings = reference.check_train(
        X, y, Xv, yv, feat=ans["feat"], thr=ans["thr"], value=ans["value"],
        gain=ans["gain"],
        vloss_prog=ans["vloss"], cfg=cell["config"], seed=seed31,
        split_rounds=split_rounds(cell["traffic"], seed))
    lim = cell["limits"]
    return {k: common.check_entry(v, lim[k]) for k, v in readings.items()}


def run(cell: Dict[str, Any], *, seed: int, seconds: float, trace: bool,
        t0: float, require_tpu: bool = True, cfg_overrides=None) -> tuple:
    dev = common.device_info(cell["chips"], require_tpu)
    conf, job = cell["config"], cell["traffic"]
    common.enable_caches()
    seed31, _ = common.seed_parts(seed)
    X, y, Xv, yv = data.train_eval(conf, seed)
    cfg = gbdt_config(cell, seed31, **(cfg_overrides or {}))
    with common.span("warmup_fit"):
        model = fit(cfg, X, y, Xv, yv)
    del model
    gc.collect()
    setup_s = time.perf_counter() - t0

    trace_dir = common.start_trace() if trace else None
    fits = []
    t_open = time.perf_counter()
    with common.span("window"):
        while not fits or time.perf_counter() - t_open < seconds:
            with common.span("fit"):
                model = fit(cfg, X, y, Xv, yv)
            fits.append(time.perf_counter())
    elapsed = fits[-1] - t_open
    red = common.stop_trace(trace_dir) if trace else None
    peak = common.memory_peak_bytes(cell["chips"])
    ans = answer(model)
    del model
    gc.collect()

    rounds = len(fits) * job["n_trees"]
    result = {"correct": None, "attempted": len(fits), "failed": 0,
              "metrics": {}, "device": dict(dev, memory_peak_bytes=peak)}
    run_ns = SimpleNamespace(
        red=red, kind=dev["kind"], rounds=rounds, fits=len(fits),
        config=conf, job=job, leaf_covers=ans["cover"], elapsed=elapsed
    ) if trace else None
    common.fill_metrics(cell, result, red, run_ns,
                        {"setup_s": setup_s,
                         "train_rounds_per_s": rounds / elapsed})
    checks = check(cell, ans, X, y, Xv, yv, seed31, seed)
    result["correct"] = all(c["ok"] for c in checks.values())
    return result, checks
