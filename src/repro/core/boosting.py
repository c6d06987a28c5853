"""SketchBoost: the gradient-boosting trainer (paper Sections 2-4).

Implements both multioutput strategies from the paper:
  * ``single_tree``  — one multivariate tree per round (CatBoost / Py-Boost style);
    the sketch accelerates its split search.  This is SketchBoost.
  * ``one_vs_all``   — d univariate trees per round (XGBoost / LightGBM style),
    implemented by vmapping the single-output grower over outputs.  This is the
    paper's baseline strategy, built in-framework for fair comparison.

Row-sampling accelerators from the Related-Work section are available as options:
uniform Stochastic Gradient Boosting (``subsample``) and GOSS (``goss_a/goss_b``),
both expressed as per-sample weights on the count channel so they compose with the
sketch.  Column sampling masks features during the split search.

Training loop
-------------
The default loop (``cfg.loop == "scan"``) compiles the *entire* boosting round
sequence as ``jax.lax.scan`` segments of ``cfg.scan_chunk`` rounds: one trace of
``_boost_round`` total, one device dispatch per segment, trees stacked into
pre-allocated ``(chunk, ...)`` forest buffers by the scan itself.  Validation
loss is computed on-device every round; the host only syncs at segment
boundaries to fold the loss trajectory into early-stopping decisions (the
"host callback boundary").  ``cfg.loop == "python"`` keeps the one-dispatch-
per-round reference loop — bit-identical forests under a fixed seed, used by
the parity tests and as a debugging fallback.  See docs/performance.md.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import forest as FO
from repro.core import guards as GU
from repro.core import histogram as H
from repro.core import losses as L
from repro.core import quantize as Q
from repro.core import sketch as SK
from repro.core import tree as T
from repro.runtime import tracing as TR


@dataclasses.dataclass(frozen=True)
class GBDTConfig:
    """Hyperparameters (defaults follow the paper's experimental setup, App. B)."""
    loss: str = "multiclass"
    n_outputs: int = 0                   # d; inferred from data when 0
    strategy: str = "single_tree"        # or "one_vs_all"
    sketch_method: str = "random_projection"   # paper's recommended default
    sketch_k: int = 5                    # paper's recommended default
    n_trees: int = 100
    depth: int = 6
    growth: str = "levelwise"            # "levelwise" (depth-wise heaps) |
                                         # "leafwise" (best-first, needs
                                         # max_leaves; depth is the bound)
    max_leaves: int = 0                  # leaf budget, leafwise only
    learning_rate: float = 0.05
    lambda_l2: float = 1.0
    n_bins: int = 256
    min_data_in_leaf: float = 1.0
    min_gain: float = 0.0
    subsample: float = 1.0               # SGB row sampling rate
    goss_a: float = 0.0                  # GOSS: keep-top fraction by |g|
    goss_b: float = 0.0                  # GOSS: random fraction of the rest
    colsample: float = 1.0               # per-tree feature sampling rate
    early_stopping_rounds: int = 0       # 0 = off
    eval_every: int = 1
    use_kernel: Any = True               # True=auto: Pallas on TPU, jnp off-TPU;
                                         # or explicit "jnp"/"pallas"/"interpret"
    hist_engine: str = "auto"            # "auto"=subtract: partitioned rows +
                                         # sibling subtraction; or explicit
                                         # "subtract"/"direct" (jnp reference)
    hist_dtype: str = "float32"          # tiles-kernel MXU input dtype;
                                         # "bfloat16" halves stats bytes
                                         # (fp32 accumulation; kernel modes
                                         # only)
    loop: str = "scan"                   # "scan" (compiled rounds) | "python"
    scan_chunk: int = 32                 # rounds per scan segment (host boundary)
    predict_row_chunk: int = 65536       # rows per predict dispatch (0 = all)
    dist_hist_compression: str = "none"  # distributed-only: route the
                                         # histogram psum through the JL
                                         # sketch ("sketch") or keep it
                                         # exact ("none")
    dist_hist_k: int = 0                 # JL width of the sketched
                                         # collective; 0 = reuse sketch_k
    guard_policy: str = "off"            # non-finite guards (core.guards):
                                         # "off" | "raise" | "skip_round" |
                                         # "clip"
    guard_clip: float = 1e6              # clamp magnitude under "clip"
    hessian_floor: float = 0.0           # per-sample hessian floor (applies
                                         # under every guard policy when > 0)
    save_every: int = 0                  # checkpoint every k round
                                         # boundaries (0 = off; needs
                                         # ckpt_dir)
    ckpt_dir: str = ""                   # checkpoint root for save_every
    ckpt_keep: int = 3                   # round checkpoints retained
    resume_from: str = ""                # checkpoint root to resume fit()
                                         # from ("" = fresh fit)
    seed: int = 0

    @property
    def dist_hist_k_effective(self) -> int:
        """JL width the sketched histogram collective actually uses."""
        return self.dist_hist_k if self.dist_hist_k > 0 else self.sketch_k

    def validate(self, *, distributed: bool = False) -> None:
        """Reject option combinations that would otherwise be silently
        ignored (the failure mode this guards: a user sets ``max_leaves``
        and the level-wise grower quietly never reads it).  The distributed
        factories (`core.distributed`) call this with ``distributed=True``
        — the single shared place config-level legality lives for both
        paths."""
        if self.growth not in ("levelwise", "leafwise"):
            raise ValueError(f"unknown growth {self.growth!r}; "
                             "expected 'levelwise' or 'leafwise'")
        if self.growth == "levelwise" and self.max_leaves:
            raise ValueError(
                f"max_leaves={self.max_leaves} is set but growth="
                "'levelwise' grows full 2^depth-leaf levels and would "
                "silently ignore it; set growth='leafwise' (best-first, "
                "honours the leaf budget) or drop max_leaves")
        if self.growth == "leafwise":
            if self.max_leaves < 2:
                raise ValueError(
                    "growth='leafwise' needs max_leaves >= 2 (the leaf "
                    f"budget of each best-first tree); got "
                    f"{self.max_leaves}")
            if self.max_leaves > 2 ** self.depth:
                raise ValueError(
                    f"max_leaves={self.max_leaves} exceeds 2^depth="
                    f"{2 ** self.depth}: the depth bound makes the extra "
                    "budget unreachable (it would be silently ignored); "
                    "raise depth or lower max_leaves")
            if self.hist_engine not in ("auto", "subtract"):
                raise ValueError(
                    f"hist_engine={self.hist_engine!r} has no leaf-wise "
                    "implementation (the best-first grower is inherently "
                    "node-partitioned with sibling subtraction); use "
                    "'auto'/'subtract' or growth='levelwise'")
        if self.hist_dtype not in ("float32", "bfloat16"):
            raise ValueError(f"unknown hist_dtype {self.hist_dtype!r}; "
                             "expected 'float32' or 'bfloat16'")
        if (self.hist_dtype == "bfloat16"
                and H.resolve_kernel_mode(self.use_kernel) == "jnp"):
            raise ValueError(
                "hist_dtype='bfloat16' rounds inside the Pallas tiles "
                "kernel; the jnp path would silently ignore it — request a "
                "kernel mode (use_kernel=True on TPU, 'interpret' for "
                "debugging) or keep hist_dtype='float32'")
        if self.dist_hist_compression not in ("none", "sketch"):
            raise ValueError(
                f"unknown dist_hist_compression "
                f"{self.dist_hist_compression!r}; expected 'none' (exact "
                "psum) or 'sketch' (JL-compressed collective)")
        if self.dist_hist_k < 0:
            raise ValueError(
                f"dist_hist_k must be >= 0, got {self.dist_hist_k}")
        if not distributed and self.dist_hist_compression != "none":
            raise ValueError(
                "dist_hist_compression='sketch' compresses the multi-device "
                "histogram collective; the single-device path has no "
                "collective and would silently ignore it — train through "
                "core.distributed (make_distributed_boost_step / "
                "fit_distributed) or keep 'none'")
        if (distributed and self.dist_hist_compression == "sketch"
                and self.dist_hist_k_effective < 1):
            raise ValueError(
                "dist_hist_compression='sketch' needs a JL width for the "
                "collective: set dist_hist_k >= 1 (or leave it 0 with "
                "sketch_k >= 1)")
        if self.guard_policy not in GU.GUARD_POLICIES:
            raise ValueError(
                f"unknown guard_policy {self.guard_policy!r}; expected one "
                f"of {GU.GUARD_POLICIES} (see core.guards)")
        if self.guard_clip <= 0.0:
            raise ValueError(
                f"guard_clip must be > 0 (the clamp magnitude for the "
                f"'clip' policy), got {self.guard_clip}")
        if self.hessian_floor < 0.0:
            raise ValueError(
                f"hessian_floor must be >= 0, got {self.hessian_floor}")
        if self.save_every < 0:
            raise ValueError(f"save_every must be >= 0, got {self.save_every}")
        if self.save_every > 0 and not self.ckpt_dir:
            raise ValueError(
                f"save_every={self.save_every} checkpoints every "
                f"{self.save_every} rounds but ckpt_dir is empty — there is "
                "nowhere to write; set ckpt_dir or save_every=0")
        if self.ckpt_keep < 1:
            raise ValueError(
                f"ckpt_keep must be >= 1 (at least the newest checkpoint "
                f"survives pruning), got {self.ckpt_keep}")

    def resolve(self, d: int) -> "GBDTConfig":
        """Validate option combinations, bind the output dimension, and pin
        the kernel mode for this process (backend auto-detection must happen
        outside jit traces so the resolved mode is part of every static
        cache key)."""
        self.validate()
        return dataclasses.replace(
            self, n_outputs=d,
            use_kernel=H.resolve_kernel_mode(self.use_kernel),
            hist_engine=H.resolve_hist_engine(self.hist_engine))

    def strip_io(self) -> "GBDTConfig":
        """Drop host-side checkpoint knobs before the config enters a jit
        static argument: two fits differing only in where/how often they
        checkpoint must share compiled executables (the loops read
        ``save_every`` from the un-stripped config on the host side)."""
        return dataclasses.replace(self, save_every=0, ckpt_dir="",
                                   ckpt_keep=3, resume_from="")


# -- input validation (actionable errors instead of jit-internal failures) ---

#: The schedule-critical hyperparameters a resumed fit must share with the
#: run that wrote the checkpoint — anything here changes gradients, sketches,
#: tree shapes, or the RNG schedule, so a mismatch breaks bit-identity.
RESUME_CFG_KEYS = (
    "loss", "strategy", "sketch_method", "sketch_k", "growth", "max_leaves",
    "depth", "n_bins", "learning_rate", "lambda_l2", "min_data_in_leaf",
    "min_gain", "subsample", "goss_a", "goss_b", "colsample", "hist_dtype",
    "guard_policy", "guard_clip", "hessian_floor", "seed")


def _resume_cfg_snapshot(cfg: GBDTConfig) -> Dict[str, Any]:
    return {k: getattr(cfg, k) for k in RESUME_CFG_KEYS}


def validate_features(X, *, n_features: Optional[int] = None,
                      where: str = "X") -> np.ndarray:
    """Check a feature matrix before it reaches the quantizer / jitted
    kernels, raising `ValueError` that names the offending axis instead of
    failing deep inside a trace.  NaN is legal (it is the missing-value
    encoding, see `quantize.MISSING_BIN`); ``+/-inf`` is not — it would
    silently land in the extreme bins.  Returns the array as float32."""
    X = np.asarray(X)
    if X.dtype.kind not in "fiub":
        raise ValueError(
            f"{where} has non-numeric dtype {X.dtype}; features must be "
            "numeric (encode categoricals first; NaN encodes missing)")
    if X.ndim != 2:
        raise ValueError(
            f"{where} must be 2-D (rows, features); got {X.ndim}-D shape "
            f"{tuple(X.shape)}")
    if n_features is not None and X.shape[1] != n_features:
        raise ValueError(
            f"{where} has {X.shape[1]} features on axis 1 but the model was "
            f"fit with {n_features}; the column layout must match training")
    X = np.ascontiguousarray(X, dtype=np.float32)
    inf_mask = np.isinf(X)
    if inf_mask.any():
        cols = np.flatnonzero(inf_mask.any(axis=0))
        raise ValueError(
            f"{where} contains {int(inf_mask.sum())} +/-inf values in "
            f"feature column(s) {cols[:8].tolist()} (axis 1); only NaN "
            "encodes missing — replace or drop the infinities")
    return X


def validate_targets(y, *, loss: str, n_rows: Optional[int] = None,
                     where: str = "y") -> np.ndarray:
    """Check targets: numeric, row-aligned with X, finite, and (for 1-D
    multiclass labels) non-negative integers."""
    y = np.asarray(y)
    if y.dtype.kind not in "fiub":
        raise ValueError(
            f"{where} has non-numeric dtype {y.dtype}; targets must be "
            "numeric")
    if y.ndim not in (1, 2):
        raise ValueError(
            f"{where} must be 1-D (class ids) or 2-D (rows, outputs); got "
            f"{y.ndim}-D shape {tuple(y.shape)}")
    if n_rows is not None and y.shape[0] != n_rows:
        raise ValueError(
            f"{where} has {y.shape[0]} rows on axis 0 but X has {n_rows}; "
            "features and targets must be row-aligned")
    if y.dtype.kind == "f":
        bad = ~np.isfinite(y)
        if bad.any():
            first = tuple(int(i) for i in np.argwhere(bad)[0])
            raise ValueError(
                f"{where} contains {int(bad.sum())} non-finite values "
                f"(first at index {first}); targets must be finite — clean "
                "them, or pass check_input=False with a guard_policy to "
                "exercise the non-finite guards deliberately")
    if loss == "multiclass" and y.ndim == 1:
        if y.dtype.kind == "f" and not np.all(y == np.floor(y)):
            raise ValueError(
                f"{where} holds 1-D multiclass labels but has non-integer "
                "values; pass integer class ids (or one-hot rows)")
        if y.size and int(y.min()) < 0:
            raise ValueError(
                f"{where} has negative class ids (min {int(y.min())}); "
                "multiclass labels must be in [0, n_classes)")
    return y


def _check_resume_compat(cfg: GBDTConfig, state) -> None:
    """Refuse to resume under a config that breaks bit-identity."""
    saved = dict(state.meta.get("train", {}).get("cfg", {}))
    want = _resume_cfg_snapshot(cfg)
    diffs = [f"{k}: checkpoint={saved[k]!r} != fit={want[k]!r}"
             for k in RESUME_CFG_KEYS if k in saved and saved[k] != want[k]]
    if diffs:
        raise ValueError(
            "resume_from checkpoint was written under a different config — "
            "the resumed rounds would not reproduce the uninterrupted run:"
            "\n  " + "\n  ".join(diffs))
    if state.round > cfg.n_trees:
        raise ValueError(
            f"resume_from checkpoint already holds {state.round} completed "
            f"rounds but cfg.n_trees={cfg.n_trees}; raise n_trees past the "
            "checkpoint to continue training")


# -- fault-injection hooks (duck-typed; see runtime.chaos) -------------------

def _as_chaos_list(chaos) -> Tuple[Any, ...]:
    if chaos is None:
        return ()
    if isinstance(chaos, (list, tuple)):
        return tuple(chaos)
    return (chaos,)


def _chaos_check(chaos, round_idx: int) -> None:
    """Fire kill-style injections whose trigger round has arrived."""
    for c in chaos:
        check = getattr(c, "check_round", None)
        if check is not None:
            check(round_idx)


def _chaos_mutate(chaos, Y, round_idx: int):
    """Apply data-corruption injections (e.g. NaN-at-row) due at or before
    ``round_idx``.  Corruption is persistent from its trigger round on."""
    for c in chaos:
        mutate = getattr(c, "mutate_targets", None)
        if mutate is not None:
            Y = mutate(Y, round_idx)
    return Y


def _next_chaos_round(chaos, done: int) -> Optional[int]:
    """Earliest chaos trigger strictly after ``done`` (scan segments are
    capped there so injections land on exact round boundaries)."""
    rounds = [int(c.round) for c in chaos
              if getattr(c, "round", None) is not None and int(c.round) > done]
    return min(rounds) if rounds else None


def _sample_weights(key: jax.Array, G: jax.Array, cfg: GBDTConfig) -> jax.Array:
    """Per-row weights implementing SGB / GOSS.  Returns (n, 1) float32."""
    n = G.shape[0]
    if cfg.goss_a > 0.0:
        # GOSS (Ke et al., 2017): keep the top a*n rows by gradient norm, sample
        # b*n of the rest, amplified by (1-a)/b to stay unbiased.
        gnorm = jnp.sum(jnp.square(G), axis=1)
        n_top = max(int(cfg.goss_a * n), 1)
        thresh = jax.lax.top_k(gnorm, n_top)[0][-1]
        top = gnorm >= thresh
        rand = jax.random.uniform(key, (n,)) < cfg.goss_b
        amp = (1.0 - cfg.goss_a) / max(cfg.goss_b, 1e-12)
        w = jnp.where(top, 1.0, jnp.where(rand, amp, 0.0))
        return w[:, None].astype(jnp.float32)
    if cfg.subsample < 1.0:
        keep = jax.random.uniform(key, (n,)) < cfg.subsample
        return keep[:, None].astype(jnp.float32)
    return jnp.ones((n, 1), jnp.float32)


def _feature_mask(key: jax.Array, m: int, cfg: GBDTConfig) -> Optional[jax.Array]:
    if cfg.colsample >= 1.0:
        return None
    return jax.random.uniform(key, (m,)) < cfg.colsample


def _boost_round(F: jax.Array, codes: jax.Array, Y: jax.Array, key: jax.Array,
                 cfg: GBDTConfig) -> Tuple[jax.Array, T.Tree]:
    """One boosting round: gradients -> sketch -> tree -> leaf values -> update F.

    Pure traceable body shared by `boost_step` (per-round jit dispatch) and
    `boost_scan` (whole-segment jit).
    """
    loss = L.get_loss(cfg.loss)
    with jax.named_scope(TR.GRAD):
        G, Hd = loss.grad_hess(F, Y)
        G, Hd, bad = GU.guard_grad_hess(G, Hd, cfg.guard_policy,
                                        cfg.guard_clip, cfg.hessian_floor)
        k_key, s_key, c_key = jax.random.split(key, 3)
        w = _sample_weights(s_key, G, cfg)
        fmask = _feature_mask(c_key, codes.shape[1], cfg)

    def grow(stats, G_t, H_t):
        """Growth-strategy dispatch: ``(tree, leaf_pos)`` for one tree."""
        kw = dict(depth=cfg.depth, n_bins=cfg.n_bins, lam=cfg.lambda_l2,
                  min_data_in_leaf=cfg.min_data_in_leaf,
                  min_gain=cfg.min_gain, feature_mask=fmask,
                  use_kernel=cfg.use_kernel)
        if cfg.growth == "leafwise":
            return T.grow_tree_leafwise(codes, stats, G_t, H_t,
                                        max_leaves=cfg.max_leaves,
                                        hist_dtype=cfg.hist_dtype, **kw)
        return T.grow_tree(codes, stats, G_t, H_t,
                           hist_engine=cfg.hist_engine,
                           hist_dtype=cfg.hist_dtype, **kw)

    if cfg.strategy == "single_tree":
        with jax.named_scope(TR.SKETCH):
            Gk = SK.build_sketch(G * w, method=cfg.sketch_method,
                                 k=cfg.sketch_k, key=k_key)
            stats = jnp.concatenate([Gk, w], axis=1)
            # Re-check after the sketch: a projection can overflow on its
            # own (inf * finite, eigh on a degenerate Gram) even from
            # finite G.
            stats, bad = GU.guard_stats(stats, cfg.guard_policy,
                                        cfg.guard_clip, bad)
        tree, leaf_pos = grow(stats, G, Hd)
        with jax.named_scope(TR.LEAF):
            if cfg.guard_policy == "skip_round":
                scale = GU.skip_scale(bad, cfg.guard_policy)
                tree = tree._replace(value=tree.value * scale,
                                     gain=tree.gain * scale)
            F = F + cfg.learning_rate * tree.value[leaf_pos]
        return F, tree

    # one_vs_all: vmap a single-output grower over the d outputs.  Each output j
    # grows its own univariate tree from (g_j, h_j); the "forest row" for this
    # round carries a (d, ...) leading axis folded into the Tree arrays.
    def grow_one(g_j, h_j):
        stats = jnp.concatenate([(g_j * w[:, 0])[:, None], w], axis=1)
        return grow(stats, g_j[:, None], h_j[:, None])

    trees, poss = jax.vmap(grow_one, in_axes=(1, 1))(G, Hd)  # (d, ...) axes
    with jax.named_scope(TR.LEAF):
        if cfg.guard_policy == "skip_round":
            # one_vs_all stats are plain (sanitized-)gradient sums — no
            # sketch projection to re-check — so the grad/hess flag alone
            # gates the round; zero every output's tree at once.
            scale = GU.skip_scale(bad, cfg.guard_policy)
            trees = trees._replace(value=trees.value * scale,
                                   gain=trees.gain * scale)
        delta = jax.vmap(lambda v, pos: v[pos, 0])(trees.value, poss)
        F = F + cfg.learning_rate * delta.T                   # delta: (d, n)
    # Fold the per-output axis into a tree whose value tensor is (d, L, 1);
    # `forest.pack_forest` later flattens the (T, d, ...) buffers into width-1
    # packed trees with per-tree output columns.
    return F, trees


def _concat_chunks(chunks):
    """Concatenate per-segment stacked tree pytrees along the round axis."""
    return (chunks[0] if len(chunks) == 1
            else jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                              *chunks))


def _as_forest(stacked):
    """Scan-stacked per-round tree pytree -> training forest container.

    Heap `tree.Tree` buffers get the `tree.Forest` wrapper; `tree.NodeTree`
    is its own stacked container (the arrays just carry a leading T axis).
    """
    if isinstance(stacked, T.NodeTree):
        return stacked
    return T.Forest(**stacked._asdict())


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(0,))
def boost_step(F: jax.Array, codes: jax.Array, Y: jax.Array, key: jax.Array,
               cfg: GBDTConfig) -> Tuple[jax.Array, T.Tree]:
    """Single-round entry point (one dispatch per tree; the reference loop)."""
    return _boost_round(F, codes, Y, key, cfg)


def _apply_tree(tree, codes: jax.Array, F: jax.Array,
                cfg: GBDTConfig) -> jax.Array:
    """Add one round's contribution to the raw scores F for new data.

    Routed through `forest.forest_apply`, the same traversal primitive the
    packed-forest serving path uses — so on-device validation eval inside
    the scan loop runs the Pallas traversal kernel whenever the split-search
    kernels do (``use_kernel`` auto-resolution), and bit-matches serving.
    Heap trees from the level-wise grower are canonicalized to the pointer
    node-list in-trace (a cheap concat); leaf-wise `tree.NodeTree` rounds
    already carry pointers.
    """
    single = cfg.strategy == "single_tree"
    if isinstance(tree, T.NodeTree):
        feat, thr = tree.feat, tree.thr
        left, right, leaf = tree.left, tree.right, tree.value
    else:
        feat, thr, left, right, leaf = T.heap_to_node_arrays(
            tree.feat, tree.thr, tree.value)
    if single:
        feat, thr, left, right, leaf = (feat[None], thr[None], left[None],
                                        right[None], leaf[None])
        out_col = jnp.zeros((1,), jnp.int32)
    else:                                    # one round = d univariate trees
        out_col = jnp.arange(feat.shape[0], dtype=jnp.int32)
    return FO.forest_apply(F, codes, feat, thr, left, right, leaf, out_col,
                           cfg.learning_rate, depth=cfg.depth,
                           mode=cfg.use_kernel)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "n_steps", "has_eval"),
                   donate_argnums=(0, 3))
def boost_scan(F: jax.Array, codes: jax.Array, Y: jax.Array,
               Fv: jax.Array, codes_v: jax.Array, Yv: jax.Array,
               key: jax.Array, *, cfg: GBDTConfig, n_steps: int,
               has_eval: bool):
    """``n_steps`` boosting rounds as one compiled ``jax.lax.scan``.

    The scan stacks every round's tree into pre-allocated ``(n_steps, ...)``
    forest buffers and — when an eval set is present — advances the validation
    scores ``Fv`` and records the validation loss *every* round, so the host
    can replay early stopping exactly from the returned trajectory without
    any per-round dispatch.

    Returns ``(F, Fv, key, trees, vloss)`` where ``trees`` is a `tree.Tree`
    whose arrays carry a leading ``n_steps`` axis and ``vloss`` is
    ``(n_steps,)`` float32 (zeros when ``has_eval`` is False).
    """
    loss = L.get_loss(cfg.loss)

    def step(carry, _):
        F, Fv, key = carry
        key, sub = jax.random.split(key)
        F, tree = _boost_round(F, codes, Y, sub, cfg)
        if has_eval:
            with jax.named_scope(TR.EVAL):
                Fv = _apply_tree(tree, codes_v, Fv, cfg)
                vloss = loss.value(Fv, Yv).astype(jnp.float32)
        else:
            vloss = jnp.float32(0.0)
        return (F, Fv, key), (tree, vloss)

    (F, Fv, key), (trees, vloss) = jax.lax.scan(step, (F, Fv, key), None,
                                                length=n_steps)
    return F, Fv, key, trees, vloss


class SketchBoost:
    """High-level estimator: fit / predict with early stopping and eval logging.

    >>> model = SketchBoost(GBDTConfig(loss="multiclass", sketch_k=5))
    >>> model.fit(X, y, eval_set=(Xv, yv))
    >>> proba = model.predict(X_test)
    """

    def __init__(self, cfg: GBDTConfig):
        self.cfg = cfg
        self.quantizer: Optional[Q.Quantizer] = None
        self.forest: Optional[T.Forest] = None
        self.packed: Optional[FO.PackedForest] = None
        self.base_score: Optional[jax.Array] = None
        self.history: List[Dict[str, Any]] = []
        self.best_round: int = -1
        self._path_pack: Any = None     # full-forest PathPack, built lazily

    # -- data prep ----------------------------------------------------------
    def _bin(self, X, check_input: bool = True, where: str = "X") -> jax.Array:
        if self.quantizer is None:
            raise ValueError(
                "model is not fitted (no quantizer); call fit() first or "
                "resume from a checkpoint")
        if check_input:
            X = validate_features(X, n_features=self.quantizer.edges.shape[0],
                                  where=where)
        return Q.apply_quantizer(self.quantizer, jnp.asarray(X, jnp.float32))

    def _targets(self, y, d: int) -> jax.Array:
        y = jnp.asarray(y)
        if self.cfg.loss == "multiclass" and y.ndim == 1:
            return y.astype(jnp.int32)
        return y.astype(jnp.float32)

    def _infer_d(self, y) -> int:
        if self.cfg.n_outputs:
            return self.cfg.n_outputs
        y = np.asarray(y)
        if self.cfg.loss == "multiclass" and y.ndim == 1:
            return int(y.max()) + 1
        return int(y.shape[1])

    def _base(self, Y, d: int) -> jax.Array:
        """Constant base score: log-priors (classification) or target mean."""
        if self.cfg.loss == "multiclass":
            if Y.ndim == 1:
                counts = jnp.bincount(Y, length=d) + 1.0
                return jnp.log(counts / counts.sum())
            return jnp.log(Y.mean(0) + 1e-6)
        if self.cfg.loss == "multilabel":
            p = jnp.clip(Y.mean(0), 1e-6, 1 - 1e-6)
            return jnp.log(p / (1 - p))
        return Y.mean(0)

    # -- training -----------------------------------------------------------
    def fit(self, X, y, eval_set: Optional[Tuple] = None,
            verbose: bool = False, *, check_input: bool = True,
            chaos=None) -> "SketchBoost":
        """Train the ensemble.

        ``check_input`` routes X/y (and the eval set) through
        `validate_features` / `validate_targets` for actionable errors;
        disable it only to deliberately feed corrupt data to the non-finite
        guards.  ``chaos`` takes `runtime.chaos` injections (or a list) for
        deterministic fault testing.  With ``cfg.save_every > 0`` the fit
        checkpoints every ``save_every`` round boundaries into
        ``cfg.ckpt_dir``; ``cfg.resume_from`` restores such a checkpoint and
        continues the run bit-identically (same data, same config).

        Under a profiler the fit shows as host spans ``sketchboost.fit``
        and its parts (`runtime.tracing`).
        """
        with TR.span(TR.FIT):
            return self._fit(X, y, eval_set, verbose, check_input, chaos)

    def _fit(self, X, y, eval_set, verbose, check_input, chaos):
        with TR.span(TR.VALIDATE):
            if check_input:
                X = validate_features(X, where="X")
                y = validate_targets(y, loss=self.cfg.loss,
                                     n_rows=X.shape[0])
            else:
                X = np.asarray(X, np.float32)
            d = self._infer_d(y)
            cfg = self.cfg.resolve(d)
        chaos = _as_chaos_list(chaos)

        state = None
        if cfg.resume_from:
            from repro.io import checkpoint as CK
            state = CK.load_boost_checkpoint(cfg.resume_from)
            _check_resume_compat(cfg, state)
            if state.quantizer is None:
                raise ValueError(
                    f"checkpoint under {cfg.resume_from!r} carries no "
                    "quantizer; resume needs the binning saved at fit time "
                    "(cfg.save_every checkpoints store it automatically)")
            # Reuse the SAVED binning and base score: refitting them on the
            # (identical) data is redundant, and any drift would silently
            # break bit-identity.
            self.quantizer = state.quantizer
            self.base_score = jnp.asarray(state.packed.base, jnp.float32)
        else:
            with TR.span(TR.QUANTILES):
                self.quantizer = Q.fit_quantizer(X, cfg.n_bins, seed=cfg.seed)
        with TR.span(TR.BIN):
            codes = self._bin(X, check_input=False)
            Y = self._targets(y, d)
            n = codes.shape[0]
            if state is None:
                self.base_score = self._base(Y, d).astype(jnp.float32)
                F = jnp.broadcast_to(self.base_score,
                                     (n, d)).astype(jnp.float32)
            elif tuple(state.F.shape) != (n, d):
                raise ValueError(
                    f"resume_from checkpoint holds training scores of shape "
                    f"{tuple(state.F.shape)} but X/y give ({n}, {d}); "
                    "resume must rerun fit() on the same training data")
            else:
                F = jnp.asarray(state.F, jnp.float32)
        has_eval = eval_set is not None
        if has_eval:
            with TR.span(TR.VALIDATE):
                Xv = (validate_features(
                          eval_set[0],
                          n_features=self.quantizer.edges.shape[0],
                          where="eval_set X")
                      if check_input else np.asarray(eval_set[0], np.float32))
                yv = (validate_targets(eval_set[1], loss=cfg.loss,
                                       n_rows=Xv.shape[0],
                                       where="eval_set y")
                      if check_input else eval_set[1])
            with TR.span(TR.BIN):
                codes_v = self._bin(Xv, check_input=False)
                Yv = self._targets(yv, d)
                nv = codes_v.shape[0]
                if state is None:
                    Fv = jnp.broadcast_to(self.base_score,
                                          (nv, d)).astype(jnp.float32)
                elif state.Fv is None:
                    raise ValueError(
                        "resume_from checkpoint was saved without an eval "
                        "set but fit() got one; the early-stopping "
                        "trajectory cannot be reconstructed — drop eval_set "
                        "or refit from scratch")
                elif tuple(state.Fv.shape) != (nv, d):
                    raise ValueError(
                        f"resume_from checkpoint holds eval scores of shape "
                        f"{tuple(state.Fv.shape)} but eval_set gives "
                        f"({nv}, {d}); resume must use the same eval set")
                else:
                    Fv = jnp.asarray(state.Fv, jnp.float32)
        else:
            if state is not None and state.Fv is not None:
                raise ValueError(
                    "resume_from checkpoint carries eval scores but fit() "
                    "got no eval_set; pass the same eval_set so early "
                    "stopping replays bit-identically")
            # Static-branch dummies: never touched when has_eval is False.
            codes_v, Yv, Fv = codes[:1], Y[:1], F[:1]

        if state is not None:
            key = state.key
            start, prefix = state.round, state.trees
            if isinstance(prefix, T.Forest):
                # The loops stack per-round `tree.Tree` pytrees; re-wrap the
                # stored Forest so prefix and new segments share a pytree
                # structure (same field names, same arrays).
                prefix = T.Tree(**prefix._asdict())
            best = (state.best_loss, state.best_round)
            self.history = list(state.history)
        else:
            key = jax.random.key(cfg.seed)
            start, prefix, best = 0, None, (np.inf, -1)
            self.history = []

        saver = self._make_saver(cfg, has_eval)
        run_cfg = cfg.strip_io()     # ckpt knobs stay out of jit cache keys
        if cfg.loop == "python":
            self._fit_python(run_cfg, F, codes, Y, Fv, codes_v, Yv, has_eval,
                             key, verbose, start=start, prefix=prefix,
                             best=best, chaos=chaos, saver=saver,
                             save_every=cfg.save_every)
        elif cfg.loop == "scan":
            self._fit_scan(run_cfg, F, codes, Y, Fv, codes_v, Yv, has_eval,
                           key, verbose, start=start, prefix=prefix,
                           best=best, chaos=chaos, saver=saver,
                           save_every=cfg.save_every)
        else:
            raise ValueError(f"unknown loop {cfg.loop!r}; "
                             "expected 'scan' or 'python'")
        self.cfg = cfg
        with TR.span(TR.PACK):
            self.packed = FO.pack_forest(
                self.forest, self.base_score, cfg.learning_rate,
                strategy=cfg.strategy,
                max_depth=cfg.depth if cfg.growth == "leafwise" else None)
        self._path_pack = None              # path slots belong to old forest
        return self

    def _make_saver(self, cfg: GBDTConfig, has_eval: bool):
        """Round-boundary checkpoint closure for the training loops (None
        when checkpointing is off).  Every save is a format-v4 step: the
        packed serving prefix plus the raw resume state."""
        if not (cfg.save_every > 0 and cfg.ckpt_dir):
            return None
        from repro.io import checkpoint as CK

        def save(round_done, stacked, F, Fv, key, best_loss, best_round,
                 history):
            forest = _as_forest(stacked)
            packed = FO.pack_forest(
                forest, self.base_score, cfg.learning_rate,
                strategy=cfg.strategy,
                max_depth=cfg.depth if cfg.growth == "leafwise" else None)
            meta = _resume_cfg_snapshot(cfg)
            meta["extra_meta"] = {
                "best_iteration": int(best_round) + 1 if best_round >= 0
                else int(round_done)}
            CK.save_boost_checkpoint(
                cfg.ckpt_dir, round_done=int(round_done), packed=packed,
                quantizer=self.quantizer, trees=forest, F=F,
                Fv=(Fv if has_eval else None), key=key, history=history,
                best_loss=float(best_loss), best_round=int(best_round),
                cfg_meta=meta, keep_n=cfg.ckpt_keep)

        return save

    def _fit_scan(self, cfg: GBDTConfig, F, codes, Y, Fv, codes_v, Yv,
                  has_eval: bool, key, verbose: bool, *, start: int = 0,
                  prefix=None, best=(np.inf, -1), chaos=(), saver=None,
                  save_every: int = 0) -> None:
        """Compiled loop: scan segments of `scan_chunk` rounds, host-side
        early-stopping replay between segments (see module docstring).
        Segments are additionally capped at checkpoint (``save_every``) and
        chaos-trigger boundaries so saves and injections land on exact round
        boundaries; ``start``/``prefix``/``best`` seed a resumed run.

        Every round of a segment records the host time at which the
        segment's results reached the host as its ``train_time_s``: the
        device is not interrupted inside a segment, so no finer time is
        measured (a segment's rounds share one time)."""
        n_total = cfg.n_trees
        chunk = cfg.scan_chunk if cfg.scan_chunk > 0 else n_total
        chunk = max(1, min(chunk, max(n_total - start, 1)))
        best_loss, best_round = best
        chunks = ([] if prefix is None else [prefix])
        done, stop = start, False
        t0 = time.perf_counter()
        while done < n_total and not stop:
            _chaos_check(chaos, done)
            Y = _chaos_mutate(chaos, Y, done)
            steps = min(chunk, n_total - done)
            if save_every > 0:
                boundary = (done // save_every + 1) * save_every
                steps = min(steps, boundary - done)
            nxt = _next_chaos_round(chaos, done)
            if nxt is not None:
                steps = min(steps, nxt - done)
            with TR.span(TR.SEGMENT):
                F, Fv, key, trees, vloss = boost_scan(
                    F, codes, Y, Fv, codes_v, Yv, key, cfg=cfg,
                    n_steps=steps, has_eval=has_eval)
            with TR.span(TR.SYNC):
                vl = np.asarray(vloss)        # host sync = segment boundary
            elapsed = time.perf_counter() - t0
            with TR.span(TR.REPLAY):
                if cfg.guard_policy == "raise":
                    GU.check_scores_host(F, done + steps - 1)
                keep = steps
                for j in range(steps):
                    it = done + j
                    rec = {"round": it, "train_time_s": elapsed}
                    if has_eval and it % cfg.eval_every == 0:
                        v = float(vl[j])
                        rec["valid_loss"] = v
                        if v < best_loss - 1e-9:
                            best_loss, best_round = v, it
                        if (cfg.early_stopping_rounds
                                and it - best_round
                                >= cfg.early_stopping_rounds):
                            self.history.append(rec)
                            keep, stop = j + 1, True
                            if verbose:
                                print(f"[sketchboost] early stop @ {it} "
                                      f"(best {best_loss:.5f} @ "
                                      f"{best_round})")
                            break
                    self.history.append(rec)
                chunks.append(jax.tree.map(lambda x: x[:keep], trees))
                done += keep
                if (saver is not None and not stop
                        and done % save_every == 0):
                    saver(done, _concat_chunks(chunks), F, Fv, key,
                          best_loss, best_round, list(self.history))
            if verbose and not stop:
                msg = f"[sketchboost] round {done - 1}"
                if has_eval:
                    msg += f" valid_loss={float(vl[keep - 1]):.5f}"
                print(msg)

        with TR.span(TR.REPLAY):
            stacked = _concat_chunks(chunks)
            if best_round >= 0 and cfg.early_stopping_rounds:
                keep_n = best_round + 1
                stacked = jax.tree.map(lambda x: x[:keep_n], stacked)
        self.best_round = (best_round if best_round >= 0
                           else stacked.feat.shape[0] - 1)
        self.forest = _as_forest(stacked)

    def _fit_python(self, cfg: GBDTConfig, F, codes, Y, Fv, codes_v, Yv,
                    has_eval: bool, key, verbose: bool, *, start: int = 0,
                    prefix=None, best=(np.inf, -1), chaos=(), saver=None,
                    save_every: int = 0) -> None:
        """Reference loop: one `boost_step` dispatch per round.  Kept for
        scan-parity tests and debugging; trains bit-identical forests."""
        loss = L.get_loss(cfg.loss)
        trees, (best_loss, best_round) = [], best
        t0 = time.perf_counter()

        def combined(new_trees):
            """Checkpoint prefix + new rounds -> one stacked pytree."""
            stacked = (jax.tree.map(lambda *xs: jnp.stack(xs), *new_trees)
                       if new_trees else None)
            if prefix is None:
                return stacked
            if stacked is None:
                return prefix
            return _concat_chunks([prefix, stacked])

        for it in range(start, cfg.n_trees):
            _chaos_check(chaos, it)
            Y = _chaos_mutate(chaos, Y, it)
            key, sub = jax.random.split(key)
            F, tree = boost_step(F, codes, Y, sub, cfg)
            if cfg.guard_policy == "raise":
                GU.check_scores_host(F, it)
            trees.append(tree)
            rec = {"round": it, "train_time_s": time.perf_counter() - t0}
            if has_eval:
                Fv = _apply_tree(tree, codes_v, Fv, cfg)
            if has_eval and it % cfg.eval_every == 0:
                vloss = float(loss.value(Fv, Yv))
                rec["valid_loss"] = vloss
                if vloss < best_loss - 1e-9:
                    best_loss, best_round = vloss, it
                if (cfg.early_stopping_rounds
                        and it - best_round >= cfg.early_stopping_rounds):
                    self.history.append(rec)
                    if verbose:
                        print(f"[sketchboost] early stop @ {it} "
                              f"(best {best_loss:.5f} @ {best_round})")
                    break
            self.history.append(rec)
            if saver is not None and (it + 1) % save_every == 0:
                saver(it + 1, combined(trees), F, Fv, key, best_loss,
                      best_round, list(self.history))
            if verbose and it % 20 == 0:
                msg = f"[sketchboost] round {it}"
                if "valid_loss" in rec:
                    msg += f" valid_loss={rec['valid_loss']:.5f}"
                print(msg)

        stacked = combined(trees)
        if best_round >= 0 and cfg.early_stopping_rounds:
            stacked = jax.tree.map(lambda x: x[:best_round + 1], stacked)
        self.best_round = (best_round if best_round >= 0
                           else stacked.feat.shape[0] - 1)
        self.forest = _as_forest(stacked)

    # -- inference ----------------------------------------------------------
    @property
    def best_iteration(self) -> int:
        """Number of boosting rounds up to (and including) the best one."""
        return self.best_round + 1

    def predict_raw(self, X, iteration: Optional[int] = None) -> jax.Array:
        """Raw scores through the packed-forest engine (chunk-streamed,
        kernel-mode dispatched).  ``iteration`` slices the ensemble to the
        first ``iteration`` rounds (e.g. ``model.best_iteration``) for free.
        """
        codes = self._bin(np.asarray(X, np.float32))
        pf = self.packed
        if iteration is not None:
            pf = FO.slice_rounds(pf, iteration)
        return FO.predict_raw(pf, codes, mode=self.cfg.use_kernel,
                              row_chunk=self.cfg.predict_row_chunk)

    def predict(self, X, iteration: Optional[int] = None) -> jax.Array:
        return L.get_loss(self.cfg.loss).transform(
            self.predict_raw(X, iteration))

    # -- explainability (repro.explain) -------------------------------------
    def _sliced_packed(self, iteration: Optional[int]) -> FO.PackedForest:
        return (self.packed if iteration is None
                else FO.slice_rounds(self.packed, iteration))

    def shap_values(self, X, *, algorithm: str = "path_dependent",
                    background=None, iteration: Optional[int] = None,
                    check_additivity: bool = False):
        """Per-output SHAP attributions ``(phi, base_values)``.

        ``phi`` is ``(n, m, d)`` — one attribution per (row, feature, output)
        — and ``base_values`` is ``(d,)``; local accuracy holds:
        ``base_values + phi.sum(axis=1) == predict_raw(X)`` (to float32
        accumulation error).  ``algorithm="path_dependent"`` is exact
        TreeSHAP over the packed per-node covers; ``"interventional"``
        explains against a ``background`` dataset (raw features, binned with
        the model's quantizer).  Runs under the model's resolved
        ``use_kernel`` mode (Pallas path-walk kernel on TPU).
        """
        from repro import explain as EX
        codes = self._bin(np.asarray(X, np.float32))
        bg = (None if background is None
              else self._bin(np.asarray(background, np.float32)))
        pf = self._sliced_packed(iteration)
        if self._path_pack is None:            # host-side extraction: once
            self._path_pack = EX.build_path_pack(self.packed)
        pack = self._path_pack
        if iteration is not None:              # pure prefix of the tree axis
            t = iteration * self.packed.trees_per_round
            pack = EX.PathPack(*(a[:t] for a in pack))
        phi, base = EX.shap_values(
            pf, codes, algorithm=algorithm, background=bg,
            mode=self.cfg.use_kernel, row_chunk=self.cfg.predict_row_chunk,
            pack=pack)
        if check_additivity:
            raw = self.predict_raw(X, iteration)
            err = float(jnp.max(jnp.abs(base + phi.sum(axis=1) - raw)))
            if err > 1e-3:
                raise AssertionError(
                    f"SHAP additivity violated: max |base + sum(phi) - "
                    f"predict_raw| = {err:.2e}")
        return phi, base

    def apply(self, X, iteration: Optional[int] = None) -> jax.Array:
        """Terminal-node embeddings: ``(n, T)`` int32 per-tree node ids in
        the packed forest's unified numbering (one-hot them over
        ``model.packed.n_nodes`` buckets).  For level-wise (heap) trees the
        id of leaf ordinal ``j`` is ``2^depth - 1 + j`` — changed from the
        pre-pointer-format leaf ordinals."""
        from repro import explain as EX
        codes = self._bin(np.asarray(X, np.float32))
        return EX.apply_forest(self._sliced_packed(iteration), codes)

    def feature_importances(self, kind: str = "gain") -> jax.Array:
        """Normalised per-feature importances from the packed buffers
        (``kind`` in {"gain", "cover", "split_count"})."""
        from repro import explain as EX
        m = self.quantizer.edges.shape[0]
        return EX.feature_importances(self.packed, kind=kind, n_features=m)

    @property
    def feature_importances_(self) -> jax.Array:
        """sklearn-style alias for gain importances."""
        return self.feature_importances("gain")

    def eval_loss(self, X, y) -> float:
        d = self.cfg.n_outputs
        return float(L.get_loss(self.cfg.loss).value(self.predict_raw(X),
                                                     self._targets(y, d)))
