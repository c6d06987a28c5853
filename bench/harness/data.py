"""Seeded tabular data at a configuration's shapes.

A copy of the program's synthetic generator (`repro.data.pipeline.
make_tabular`, the Guyon-style protocol of the paper's App. B.7), kept
here so that a later change to the program cannot change the benchmark's
inputs.  Informative features are i.i.d. normals, then twice as many
linear combinations of them, then pure noise; targets come from a random
linear map of the informative features plus noise.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


def make_tabular(task: str, n: int, m: int, d: int, *, seed: int,
                 n_informative: Optional[int] = None, noise: float = 0.5
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """``(X, y)``: X (n, m) float32; y (n,) int32 class ids for
    ``multiclass``, (n, d) float32 {0, 1} for ``multilabel``."""
    rng = np.random.default_rng(seed)
    ni = n_informative or max(m // 10, 2)
    nc = min(2 * ni, max(m - ni, 0))
    base = rng.normal(size=(n, ni)).astype(np.float32)
    combo = base @ rng.normal(size=(ni, nc)).astype(np.float32)
    rest = rng.normal(size=(n, max(m - ni - nc, 0))).astype(np.float32)
    X = np.concatenate([base, combo, rest], axis=1)[:, :m]
    W = rng.normal(size=(ni, d)).astype(np.float32)
    logits = base @ W + noise * rng.normal(size=(n, d)).astype(np.float32)
    if task == "multiclass":
        y = logits.argmax(1).astype(np.int32)
    elif task == "multilabel":
        y = (logits > 0).astype(np.float32)
    else:
        raise ValueError(f"unknown task {task!r}")
    return X, y


def train_eval(cfg: dict, seed: int):
    """The configuration's train and eval sets, made from ``seed``."""
    n, nv = cfg["n_train"], cfg["n_eval"]
    X, y = make_tabular(cfg["task"], n + nv, cfg["n_features"],
                        cfg["n_outputs"], seed=seed)
    return X[:n], y[:n], X[n:], y[n:]
