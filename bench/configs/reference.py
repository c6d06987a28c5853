"""Plain reference of the configurations' semantics, for the check that
decides ``correct``.

It imports nothing of the program and takes nothing the program made: it
bins the raw features itself, draws the sketch from the seed itself and
computes every gradient, histogram, gain, leaf value and loss in float32
(one-hot contractions at ``Precision.HIGHEST``, whose products with 0/1
are exact and whose sums are float32 sums).  What it takes from the program is the answer under check:
the trees the timed fit produced and the eval losses it reported.

Semantics (SketchBoost, single-tree strategy, level-wise growth; the
configuration files state the numbers):

* binning: per feature, the quantiles at ``j / (n_bins - 1)``,
  ``j = 1 .. n_bins - 2``, of a ``sample_rows`` uniform row subsample
  drawn without replacement from the seed (all rows when fewer), then
  ``+inf``; a value's code is ``1 +`` the number of edges below it, NaN is
  code 0; a split ``(f, b)`` sends a row left when its code is ``<= b``;
* base score: log class priors with one pseudo-count (multiclass), the
  logit of the clipped label means (multilabel);
* round ``t``: key schedule ``key = key(seed)``, ``key, sub = split(key)``,
  ``k_key = split(sub, 3)[0]``; gradients and diagonal Hessians of the loss
  at the current scores; the sketch ``G @ Pi`` with ``Pi ~ N(0, 1/k)``
  drawn from ``k_key`` (``G`` itself when ``k >= d`` or the sketch is
  ``none``), plus a count
  channel; at every node, the split
  ``(f, b)`` with the largest ``0.5 (S_l + S_r - S_p)``, ``S = |sum g|^2 /
  (count + lambda)``, among bins ``b < n_bins - 1`` leaving at least
  ``min_data_in_leaf`` rows on each side, or no split when none has a
  positive gain; leaf values ``-sum G / (sum H + lambda)``; scores
  ``F += lr * value[leaf]``.
"""
from __future__ import annotations

import functools
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


# -- binning ----------------------------------------------------------------

def quantile_edges(X: np.ndarray, n_bins: int, seed: int,
                   sample_rows: int = 200_000) -> np.ndarray:
    n = X.shape[0]
    if n > sample_rows:
        X = X[np.random.default_rng(seed).choice(n, sample_rows,
                                                 replace=False)]
    qs = np.linspace(0.0, 1.0, n_bins)[1:-1]
    with np.errstate(all="ignore"):
        edges = np.nanquantile(X.astype(np.float64), qs, axis=0).T
    edges = np.concatenate([edges, np.full((X.shape[1], 1), np.inf)], 1)
    return np.nan_to_num(edges, nan=np.inf, posinf=np.inf).astype(np.float32)


def bin_codes(X: np.ndarray, edges: np.ndarray) -> np.ndarray:
    codes = np.empty(X.shape, np.uint8)
    for f in range(X.shape[1]):
        c = np.searchsorted(edges[f], X[:, f], side="left") + 1
        codes[:, f] = np.where(np.isnan(X[:, f]), 0, c)
    return codes


# -- losses -----------------------------------------------------------------

def targets(y, task: str, d: int) -> jax.Array:
    if task == "multiclass":
        return jax.nn.one_hot(jnp.asarray(y, jnp.int32), d,
                              dtype=jnp.float32)
    return jnp.asarray(y, jnp.float32)


def base_score(Y: jax.Array, task: str) -> jax.Array:
    if task == "multiclass":
        counts = Y.sum(0) + 1.0
        return jnp.log(counts / counts.sum())
    p = jnp.clip(Y.mean(0), 1e-6, 1 - 1e-6)
    return jnp.log(p / (1 - p))


def grad_hess(F: jax.Array, Y: jax.Array, task: str):
    P = (jax.nn.softmax(F, axis=-1) if task == "multiclass"
         else jax.nn.sigmoid(F))
    return P - Y, P * (1.0 - P)


def loss_value(F: jax.Array, Y: jax.Array, task: str) -> jax.Array:
    if task == "multiclass":
        return -jnp.mean(jnp.sum(Y * jax.nn.log_softmax(F, axis=-1), -1))
    return jnp.mean(jnp.maximum(F, 0) - F * Y
                    + jnp.log1p(jnp.exp(-jnp.abs(F))))


def projection_keys(seed: int, n_rounds: int) -> list:
    key, out = jax.random.key(seed), []
    for _ in range(n_rounds):
        key, sub = jax.random.split(key)
        out.append(jax.random.split(sub, 3)[0])
    return out


# -- exact one-hot contractions ---------------------------------------------

def onehot_dot(onehot_t: jax.Array, x: jax.Array) -> jax.Array:
    """``onehot_t @ x`` for a 0/1 ``onehot_t`` at float32 precision: on the
    TPU, ``HIGHEST`` splits each float32 operand into bfloat16 parts, so
    every product with a 0/1 entry is exact and the sums accumulate in
    float32."""
    return jnp.dot(onehot_t, x, precision=HI,
                   preferred_element_type=jnp.float32)


def route(codes: jax.Array, feat: jax.Array, thr: jax.Array, depth: int):
    """Per level, each row's node within the level; and its leaf."""
    n = codes.shape[0]
    pos = jnp.zeros((n,), jnp.int32)
    levels = []
    for lvl in range(depth):
        levels.append(pos)
        node = (2 ** lvl - 1) + pos
        code = codes[jnp.arange(n), feat[node]].astype(jnp.int32)
        pos = 2 * pos + (code > thr[node]).astype(jnp.int32)
    return levels, pos


def leaf_sums(leaf: jax.Array, x: jax.Array, n_leaves: int) -> jax.Array:
    oh_t = (jnp.arange(n_leaves)[:, None] == leaf[None, :]).astype(
        jnp.float32)
    return onehot_dot(oh_t, x)


def level_hist(codes: jax.Array, node: jax.Array, stats: jax.Array,
               n_nodes: int, n_bins: int) -> jax.Array:
    """(n_nodes, m, n_bins, c) sums of ``stats`` per node, feature and
    bin."""
    c = stats.shape[1]
    spread = (jax.nn.one_hot(node, n_nodes, dtype=jnp.float32)[:, :, None]
              * stats[:, None, :]).reshape(stats.shape[0], n_nodes * c)

    def one_feature(col):
        oh_t = (jnp.arange(n_bins)[:, None] == col[None, :].astype(
            jnp.int32)).astype(jnp.float32)
        return onehot_dot(oh_t, spread)            # (n_bins, n_nodes * c)

    h = jax.lax.map(one_feature, codes.T)          # (m, n_bins, nodes * c)
    return h.reshape(h.shape[0], n_bins, n_nodes, c).transpose(2, 0, 1, 3)


def gains(hist: jax.Array, lam: float, min_data: float) -> jax.Array:
    """(nodes, m, n_bins) split gains; -inf where illegal."""
    csum = jnp.cumsum(hist, axis=2)
    total = csum[:, :, -1:, :]
    gl, cl = csum[..., :-1], csum[..., -1]
    gr, cr = total[..., :-1] - gl, total[..., -1] - cl
    s_l = jnp.sum(gl * gl, -1) / (cl + lam)
    s_r = jnp.sum(gr * gr, -1) / (cr + lam)
    s_p = jnp.sum(total[..., :-1] ** 2, -1) / (total[..., -1] + lam)
    gain = 0.5 * (s_l + s_r - s_p)
    n_bins = hist.shape[2]
    legal = ((jnp.arange(n_bins) < n_bins - 1)[None, None, :]
             & (cl >= min_data) & (cr >= min_data))
    return jnp.where(legal, gain, -jnp.inf)


# -- training check -----------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("task", "depth", "lr", "lam"))
def _round(F, Fv, codes, codes_v, Y, Yv, feat, thr, value_prog, *, task,
           depth, lr, lam):
    """Leaf values of the program's tree from the reference's gradients,
    their gap to the program's, and the scores and eval loss after it."""
    G, Hd = grad_hess(F, Y, task)
    _, leaf = route(codes, feat, thr, depth)
    n_leaves = 2 ** depth
    value = -leaf_sums(leaf, G, n_leaves) / (
        leaf_sums(leaf, Hd, n_leaves) + lam)
    ref_norm = jnp.linalg.norm(value, axis=1)
    scale = jnp.maximum(ref_norm, jnp.median(ref_norm))
    leaf_gap = jnp.max(jnp.linalg.norm(value_prog - value, axis=1)
                       / jnp.maximum(scale, 1e-30))
    F = F + lr * value[leaf]
    _, leaf_v = route(codes_v, feat, thr, depth)
    Fv = Fv + lr * value[leaf_v]
    return F, Fv, leaf_gap, loss_value(Fv, Yv, task)


@functools.partial(jax.jit,
                   static_argnames=("task", "depth", "n_bins", "lam",
                                    "min_data"))
def _split_gaps(F, codes, Y, pi, feat, thr, *, task, depth, n_bins, lam,
                min_data):
    """Per heap node: the best gain the reference finds, how far the
    program's split falls below it (inf where the program's split is
    illegal), and the reference's gain of the program's split (0 where the
    program made no split)."""
    G, _ = grad_hess(F, Y, task)
    stats = jnp.concatenate([jnp.dot(G, pi, precision=HI),
                             jnp.ones((G.shape[0], 1), jnp.float32)], 1)
    levels, _ = route(codes, feat, thr, depth)
    best_all, gap_all, chosen_all = [], [], []
    for lvl, node in enumerate(levels):
        n_nodes = 2 ** lvl
        g = gains(level_hist(codes, node, stats, n_nodes, n_bins), lam,
                  min_data)                          # (nodes, m, B)
        best = jnp.max(g.reshape(n_nodes, -1), axis=1)
        ids = (2 ** lvl - 1) + jnp.arange(n_nodes)
        f_p, b_p = feat[ids], thr[ids]
        chosen = g[jnp.arange(n_nodes), f_p, b_p]
        no_split = b_p == n_bins - 1
        best_pos = jnp.where(jnp.isfinite(best), jnp.maximum(best, 0.0), 0.0)
        gap = jnp.where(no_split, best_pos,
                        jnp.where(jnp.isfinite(chosen),
                                  jnp.maximum(best_pos - chosen, 0.0),
                                  jnp.inf))
        best_all.append(best_pos)
        gap_all.append(gap)
        chosen_all.append(jnp.where(no_split, 0.0, chosen))
    return (jnp.concatenate(best_all), jnp.concatenate(gap_all),
            jnp.concatenate(chosen_all))


def relative_gaps(best: np.ndarray, gap: np.ndarray, chosen: np.ndarray,
                  gain_prog: np.ndarray):
    """Over one tree's nodes, each measured against ``max(best gain of the
    node, median positive best gain of the tree)``: the widest split gap
    (an illegal split reads 1) and the widest gap between the gain the
    program reports for its split and the reference's gain of that split
    (an illegal split reads 1)."""
    pos = best[best > 0]
    med = float(np.median(pos)) if pos.size else 0.0
    scale = np.maximum(np.maximum(best, med), 1e-30)
    split = np.where(np.isinf(gap), 1.0, gap / scale)
    gain = np.where(np.isfinite(chosen),
                    np.abs(gain_prog - np.where(np.isfinite(chosen),
                                                chosen, 0.0)) / scale, 1.0)
    return float(np.max(split)), float(np.max(gain))


def check_train(X, y, Xv, yv, *, feat, thr, value, gain, vloss_prog,
                cfg: Dict, seed: int, split_rounds: Sequence[int]
                ) -> Dict[str, float]:
    """Readings of the check of one fit: ``split_gap`` (widest relative
    gain gap over the nodes of the rounds in ``split_rounds``),
    ``gain_gap`` (widest relative gap between the gains the program
    reports for its splits and the reference's, same rounds),
    ``leaf_gap`` (widest relative leaf-value gap over every round) and
    ``vloss_gap`` (widest relative gap of the reported eval loss)."""
    g = cfg["gbdt"]
    task, d = cfg["task"], cfg["n_outputs"]
    if g["sketch_method"] not in ("none", "random_projection"):
        raise ValueError(f"no reference for sketch {g['sketch_method']!r}")
    k = d if g["sketch_method"] == "none" else g["sketch_k"]
    depth, n_bins = g["depth"], g["n_bins"]
    lr, lam = float(np.float32(g["learning_rate"])), float(g["lambda_l2"])
    edges = quantile_edges(X, n_bins, seed)
    codes = jnp.asarray(bin_codes(X, edges))
    codes_v = jnp.asarray(bin_codes(Xv, edges))
    Y, Yv = targets(y, task, d), targets(yv, task, d)
    base = base_score(Y, task)
    F = jnp.broadcast_to(base, Y.shape).astype(jnp.float32)
    Fv = jnp.broadcast_to(base, Yv.shape).astype(jnp.float32)
    n_rounds = feat.shape[0]
    keys = projection_keys(seed, n_rounds)
    split_gap, gain_gap, leaf_gap, vloss_gap = 0.0, 0.0, 0.0, 0.0
    for t in range(n_rounds):
        ft, tt = jnp.asarray(feat[t]), jnp.asarray(thr[t])
        if t in split_rounds:
            pi = (jax.random.normal(keys[t], (d, k), jnp.float32)
                  / jnp.sqrt(jnp.float32(k)) if k < d
                  else jnp.eye(d, dtype=jnp.float32))
            best, gap, chosen = _split_gaps(
                F, codes, Y, pi, ft, tt, task=task, depth=depth,
                n_bins=n_bins, lam=lam, min_data=float(g["min_data_in_leaf"]))
            sg, gg = relative_gaps(np.asarray(best), np.asarray(gap),
                                   np.asarray(chosen),
                                   np.asarray(gain[t], np.float64))
            split_gap, gain_gap = max(split_gap, sg), max(gain_gap, gg)
        F, Fv, lg, vl = _round(F, Fv, codes, codes_v, Y, Yv, ft, tt,
                               jnp.asarray(value[t]), task=task, depth=depth,
                               lr=lr, lam=lam)
        leaf_gap = max(leaf_gap, float(lg))
        vl = float(vl)
        vloss_gap = max(vloss_gap, abs(float(vloss_prog[t]) - vl) / abs(vl))
    return {"split_gap": split_gap, "gain_gap": gain_gap,
            "leaf_gap": leaf_gap, "vloss_gap": vloss_gap}
