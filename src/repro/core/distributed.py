"""Multi-pod distributed SketchBoost (shard_map + explicit collectives).

Layout on the production mesh (pod, data, model):
  rows    n -> sharded over ("pod", "data")   [2 x 16 = 32-way row parallelism]
  outputs d -> sharded over "model"           [16-way output parallelism]
  features m -> optionally sharded over "model" during histogramming
              (``feature_shard=True`` — the hillclimbed layout, see §Perf)

The distributed grower runs the SAME engines as the single-device path —
the node-partitioned level engine with sibling subtraction (`grow` PR-4),
the leaf-wise best-first grower (PR-5 via `tree.grow_tree_leafwise` with
``psum_axes``), both strategies, and every sketch method — with collectives
inserted at exactly the decision points:

  1. gradients           — local; softmax CE needs a model-axis logsumexp psum.
  2. sketch G_k = G @ Pi — local matmul + psum(model): the paper's technique *is*
     the gradient-compression collective; split search becomes replicated-cheap.
  3. histograms          — psum over the row axes; bytes ~ nodes*m*B*(k+1),
     i.e. d/k times smaller than an unsketched single-tree round.  Each
     shard carries its own `histogram.LevelState` — the row partition is
     advanced per level by the same O(n) stable radix step as the
     single-device engine, never re-derived from raw rows — and under the
     subtraction engine builds only the GLOBALLY smaller child of every
     parent (per-node counts psummed: 2^l ints, negligible) into a compact
     ``(n_nodes/2, ...)`` buffer whose psum moves HALF the bytes; the
     sibling is ``parent − built`` from the replicated previous level.
     With ``cfg.dist_hist_compression = "sketch"`` the gradient channels of
     this psum are routed through the JL machinery of
     `distributed.compression` (`sketched_hist_psum`): psum(G @ Pi) ==
     psum(G) @ Pi, so compressing before the collective reconstructs the
     same projection of the exact psum at ``(k+1)/(c)`` of the bytes.  The
     count channel is always summed exactly (split legality and
     smaller-child choices stay exact).
  4. split search        — replicated (or feature-sharded: local argmax +
     all_gather of per-node winners over "model").
  5. leaf values         — segment-sum on the *full* sharded gradients, psum over
     row axes only (never sketched); leaf values stay sharded over "model".

Numerics / parity envelope (asserted by tests/test_distributed_parity.py):
split DECISIONS (features, thresholds, topology) match the single-device
grower exactly at fixed seeds; histogram and leaf-value BITS match exactly
whenever every fp32 addition is exact (e.g. dyadic-valued gradients — the
parity suite's bit-identity fixtures) and otherwise differ only by
reassociation of the psum tree (~1 ulp per level, values asserted to
1e-5).  ``hist_dtype="bfloat16"`` is honoured by rounding the split-search
stats to bf16 before accumulation — the same elementwise rounding the
tiles kernel applies at its MXU input, under the same
`GBDTConfig.validate` legality rule.  See docs/distributed.md.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import forest as FO
from repro.core import guards as GU
from repro.core import histogram as H
from repro.core import sketch as SK
from repro.core import split as S
from repro.core import tree as T
from repro.core.boosting import (GBDTConfig, _as_forest, _concat_chunks,
                                 _check_resume_compat, _resume_cfg_snapshot)
from repro.distributed import compression as C


# ---------------------------------------------------------------------------
# Sharded losses: outputs (d) sharded over `model_axis`; labels replicated on
# model shards (multiclass) or sharded with F (dense targets).
# ---------------------------------------------------------------------------

def sharded_softmax(F_local: jax.Array, model_axis: str) -> jax.Array:
    m = jax.lax.pmax(jnp.max(F_local, axis=-1, keepdims=True), model_axis)
    e = jnp.exp(F_local.astype(jnp.float32) - m)
    z = jax.lax.psum(jnp.sum(e, axis=-1, keepdims=True), model_axis)
    return e / z


def sharded_grad_hess(loss_name: str, F_local: jax.Array, Y_local: jax.Array,
                      model_axis: str, d_local: int):
    """(G, H) diagonal blocks for this shard's output slice."""
    if loss_name == "multiclass":
        # Y_local: integer labels (n_loc,), replicated across model shards.
        Pm = sharded_softmax(F_local, model_axis)
        off = jax.lax.axis_index(model_axis) * d_local
        cols = off + jnp.arange(d_local)
        onehot = (Y_local[:, None] == cols[None, :]).astype(jnp.float32)
        return Pm - onehot, Pm * (1.0 - Pm)
    if loss_name == "multilabel":
        Pm = jax.nn.sigmoid(F_local.astype(jnp.float32))
        return Pm - Y_local, Pm * (1.0 - Pm)
    if loss_name == "multitask_mse":
        G = F_local.astype(jnp.float32) - Y_local
        return G, jnp.ones_like(G)
    raise ValueError(f"unknown loss {loss_name!r}")


def sharded_loss_value(loss_name: str, F_local, Y_local, model_axis: str,
                       row_axes: Sequence[str], d_local: int) -> jax.Array:
    """Mean loss over the full (sharded) batch — replicated scalar."""
    if loss_name == "multiclass":
        m = jax.lax.pmax(jnp.max(F_local, axis=-1, keepdims=True), model_axis)
        lse = jnp.log(jax.lax.psum(
            jnp.sum(jnp.exp(F_local - m), -1, keepdims=True), model_axis)) + m
        off = jax.lax.axis_index(model_axis) * d_local
        cols = off + jnp.arange(d_local)
        onehot = (Y_local[:, None] == cols[None, :]).astype(jnp.float32)
        picked = jax.lax.psum(jnp.sum(onehot * F_local, -1, keepdims=True),
                              model_axis)
        per_row = (lse - picked)[:, 0]
        total = jnp.sum(per_row)
        count = jnp.float32(per_row.shape[0])
    elif loss_name == "multilabel":
        Fl = F_local.astype(jnp.float32)
        v = jnp.maximum(Fl, 0) - Fl * Y_local + jnp.log1p(jnp.exp(-jnp.abs(Fl)))
        total = jax.lax.psum(jnp.sum(v), model_axis)
        count = jax.lax.psum(jnp.float32(v.size), model_axis)
    elif loss_name == "multitask_mse":
        v = 0.5 * jnp.square(F_local.astype(jnp.float32) - Y_local)
        total = jax.lax.psum(jnp.sum(v), model_axis)
        count = jax.lax.psum(jnp.float32(v.size), model_axis)
    else:
        raise ValueError(loss_name)
    for ax in row_axes:
        total = jax.lax.psum(total, ax)
        count = jax.lax.psum(count, ax)
    return total / count


# ---------------------------------------------------------------------------
# Histogram collectives.
# ---------------------------------------------------------------------------

def _psum_all(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    for ax in axes:
        x = jax.lax.psum(x, ax)
    return x


def sketched_hist_psum(hist: jax.Array, key: jax.Array,
                       row_axes: Sequence[str], k: int) -> jax.Array:
    """All-reduce a ``(..., c)`` histogram payload with JL-compressed
    gradient channels.

    The last axis is ``[g_1 .. g_{c-1} | count]``.  The gradient channels
    are compressed with the shared-key JL matrix ``Pi`` (replicated for
    free, same trick as `core.sketch`), psummed at ``k/(c-1)`` of the
    bytes, and reconstructed by least-squares (`compression.decompress_
    block` — the contractive projector).  Because both the psum and the
    sketch are linear, ``psum(g @ Pi) == psum(g) @ Pi``: the reconstruction
    is the orthogonal projection of the EXACT psum onto colspace(Pi), not a
    noisy per-shard estimate.  The count channel is always exact, so split
    legality (``min_data_in_leaf``) and smaller-child choices are
    unaffected.  When ``c - 1 <= k`` the compressor is the identity and
    this is an exact psum.
    """
    c = hist.shape[-1]
    g, cnt = hist[..., :-1], hist[..., -1:]
    sk, Pi, shape = C.compress_block(g.reshape(-1, c - 1), key, k)
    g_hat = C.decompress_block(_psum_all(sk, row_axes), Pi,
                               shape).reshape(g.shape)
    return jnp.concatenate([g_hat, _psum_all(cnt, row_axes)], axis=-1)


def round_collective_bytes(cfg: GBDTConfig, m: int, d: int) -> Dict[str, int]:
    """Analytic histogram-collective payload of ONE boosting round (fp32).

    Returns bytes per reduce direction per shard: ``exact_bytes`` is the
    payload the configured sketch produces without collective compression,
    ``moved_bytes`` what actually crosses the wire under
    ``dist_hist_compression``, and ``full_bytes`` the unsketched
    (``sketch_method="none"``) reference — so
    ``moved_bytes / full_bytes <= (k + 1) / (d + 1)`` is the paper's
    communication story restated for collectives (asserted by the bench).
    Counts only the dominant histogram psums; per-node count psums
    (``2^l`` ints/level) and the sketch's own model-axis psum are O(d·k)
    and negligible next to ``nodes·m·B·c``.
    """
    B = cfg.n_bins
    if cfg.strategy == "one_vs_all":
        c_full, trees = 2, d            # per-output stats: [g | count]
    else:
        k = cfg.sketch_k
        sketched = cfg.sketch_method != "none" and 0 < k < d
        c_full, trees = (k + 1 if sketched else d + 1), 1
    if cfg.growth == "leafwise":
        # Root build (1 node) + one smaller-child node per expansion.
        cells = cfg.max_leaves * m * B
    else:
        subtract = H.resolve_hist_engine(cfg.hist_engine) == "subtract"
        cells = 0
        for lvl in range(cfg.depth):
            nodes = 2 ** lvl
            built = nodes // 2 if (subtract and lvl > 0) else nodes
            cells += built * m * B
    c_moved = c_full
    if cfg.dist_hist_compression == "sketch":
        c_moved = min(c_full - 1, cfg.dist_hist_k_effective) + 1
    full_bytes = (4 * cells * (d + 1) if cfg.strategy == "single_tree"
                  else 4 * cells * 2 * d)
    return {
        "hist_cells": cells * trees,
        "exact_bytes": 4 * cells * c_full * trees,
        "moved_bytes": 4 * cells * c_moved * trees,
        "full_bytes": full_bytes,
    }


# ---------------------------------------------------------------------------
# The distributed boosting round.
# ---------------------------------------------------------------------------

def make_distributed_boost_step(mesh: Mesh, cfg: GBDTConfig, *,
                                row_axes: Tuple[str, ...] = ("data",),
                                model_axis: str = "model",
                                feature_shard: bool = False):
    """Build the jitted multi-device boosting round.

    Returns ``step(F, codes, Y, key) -> (F', tree)`` where F is (n, d) sharded
    (rows over ``row_axes``, outputs over ``model_axis``), codes is (n, m) rows-
    sharded, Y is labels (n,) or dense (n, d) sharded like F.  The returned
    tree (`tree.Tree` level-wise / `tree.NodeTree` leaf-wise; a leading
    ``d`` axis under one_vs_all) has replicated structure arrays and
    model-sharded leaf values.

    Feature parity with `boosting.boost_step`: both growth modes, both
    strategies, all five sketch methods, and ``hist_dtype="bfloat16"``
    (same kernel-mode legality rule) — at matching key derivation, so a
    fixed seed grows the same forest as the single-device step (split
    structure exact; see the module docstring for the value envelope).
    SGB/GOSS row sampling and ``colsample`` are still single-device-only
    (their keys are burned compatibly so adding them cannot shift parity).
    """
    cfg.validate(distributed=True)
    if feature_shard and cfg.strategy == "one_vs_all":
        raise ValueError(
            "feature_shard=True shards the histogram feature axis over the "
            "model axis, which one_vs_all already uses for its per-output "
            "trees — the two layouts conflict; use strategy='single_tree' "
            "or feature_shard=False")
    if feature_shard and cfg.growth == "leafwise":
        raise ValueError(
            "feature_shard=True has no leaf-wise implementation (the "
            "best-first frontier would need a per-expansion winner gather); "
            "use growth='levelwise' or feature_shard=False")
    tp = mesh.shape[model_axis]
    row_spec = P(row_axes)
    f_spec = P(row_axes, model_axis)
    y_spec = row_spec if cfg.loss == "multiclass" else f_spec
    engine = H.resolve_hist_engine(cfg.hist_engine)
    comp = cfg.dist_hist_compression
    k_comp = cfg.dist_hist_k_effective
    depth, B = cfg.depth, cfg.n_bins
    lam = jnp.float32(cfg.lambda_l2)
    min_data = jnp.float32(cfg.min_data_in_leaf)
    min_gain_ = jnp.float32(cfg.min_gain)
    raxes = tuple(row_axes)

    def hist_psum(h, key):
        if comp == "sketch":
            return sketched_hist_psum(h, key, raxes, k_comp)
        return _psum_all(h, raxes)

    def maybe_bf16(stats):
        # The tiles kernel rounds its MXU input to bf16 elementwise; the
        # distributed jnp builds apply the same rounding once per round
        # (identical values — rounding is elementwise and deterministic).
        if cfg.hist_dtype == "bfloat16":
            return stats.astype(jnp.bfloat16).astype(jnp.float32)
        return stats

    def grow_levelwise(codes_l, codes_h, stats, f_off, round_key):
        """Partition-carrying level loop; returns heap arrays + leaf_pos.

        ``codes_h`` is the histogram view of the features (a model-axis
        slice under ``feature_shard``); routing always uses the full local
        ``codes_l``.  The per-shard `LevelState` is advanced by the same
        stable radix step as the single-device engine — node membership is
        never re-derived from raw rows.
        """
        n_loc = codes_l.shape[0]
        heap_feat = jnp.zeros((2 ** depth - 1,), jnp.int32)
        heap_thr = jnp.full((2 ** depth - 1,), B - 1, jnp.int32)
        heap_gain = jnp.zeros((2 ** depth - 1,), jnp.float32)
        node_pos = jnp.zeros((n_loc,), jnp.int32)
        state = H.init_level_state(n_loc) if engine != "direct" else None
        prev_hist = None
        for lvl in range(depth):
            n_nodes = 2 ** lvl
            ck = (jax.random.fold_in(round_key, lvl) if comp == "sketch"
                  else None)
            if engine == "subtract" and lvl > 0:
                # Globally-consistent smaller-child choice from psummed
                # per-node counts (2^l ints — negligible next to hists).
                g_counts = _psum_all(state.counts, raxes)
                side, _ = H.smaller_children(g_counts)
                # Build ONLY the globally-smaller children, compacted to
                # parent index over a FULL local buffer: this shard may own
                # mostly rows of the globally-smaller side, so the
                # single-device n//2 buffer could silently drop rows.
                built = H.build_level_built(codes_h, stats, state, side,
                                            n_nodes=n_nodes, n_bins=B,
                                            n_build=n_loc)
                built = hist_psum(built, ck)          # half-size collective
                hist = H.interleave_children(side, built, prev_hist - built)
            elif engine == "direct":
                hist = hist_psum(H.build_histograms_jnp(
                    codes_h, node_pos, stats, n_nodes=n_nodes, n_bins=B), ck)
            else:
                hist = hist_psum(H.build_level_jnp(
                    codes_h, stats, state, None, n_nodes=n_nodes, n_bins=B,
                    subtract=False), ck)
            prev_hist = hist
            gain = S.split_scores(hist, lam, min_data)
            sp = S.best_splits(gain, min_gain_)
            if feature_shard:
                # Local winner per node -> global winner over the model axis.
                local_best = jnp.stack(
                    [sp.gain, (sp.feat + f_off).astype(jnp.float32),
                     sp.thr.astype(jnp.float32)], axis=-1)     # (nodes, 3)
                allb = jax.lax.all_gather(local_best, model_axis)
                winner = jnp.argmax(allb[..., 0], axis=0)      # (nodes,)
                picked = jnp.take_along_axis(
                    allb, winner[None, :, None], axis=0)[0]    # (nodes, 3)
                feat = picked[:, 1].astype(jnp.int32)
                thr = picked[:, 2].astype(jnp.int32)
                g_out = picked[:, 0]
                is_leaf = ~(g_out > cfg.min_gain)
                feat = jnp.where(is_leaf, 0, feat)
                thr = jnp.where(is_leaf, B - 1, thr)
                sp = S.Splits(feat=feat, thr=thr,
                              gain=jnp.where(is_leaf, 0.0, g_out),
                              is_leaf=is_leaf)
            off = n_nodes - 1
            heap_feat = jax.lax.dynamic_update_slice(heap_feat, sp.feat,
                                                     (off,))
            heap_thr = jax.lax.dynamic_update_slice(heap_thr, sp.thr, (off,))
            heap_gain = jax.lax.dynamic_update_slice(heap_gain, sp.gain,
                                                     (off,))
            bits = T.route_bits(codes_l, node_pos, sp.feat, sp.thr)
            node_pos = node_pos * 2 + bits
            if state is not None and lvl < depth - 1:
                state = H.advance_level_state(state, bits)
        return heap_feat, heap_thr, heap_gain, node_pos

    def leaf_pass(node_pos, G_t, H_t, n_leaves):
        """Exact full-gradient leaf values: psum over rows only."""
        n_loc = node_pos.shape[0]
        g_sum, h_sum = H.leaf_sums(node_pos, G_t, H_t, n_leaves=n_leaves)
        cover = jax.ops.segment_sum(jnp.ones((n_loc,), jnp.float32),
                                    node_pos, num_segments=n_leaves)
        g_sum = _psum_all(g_sum, raxes)
        h_sum = _psum_all(h_sum, raxes)
        cover = _psum_all(cover, raxes)
        return -g_sum / (h_sum + lam), cover

    def grow_leafwise(codes_l, stats, G_t, H_t, comp_key):
        return T.grow_tree_leafwise(
            codes_l, stats, G_t, H_t, depth=depth,
            max_leaves=cfg.max_leaves, n_bins=B, lam=cfg.lambda_l2,
            min_data_in_leaf=cfg.min_data_in_leaf, min_gain=cfg.min_gain,
            use_kernel=False, psum_axes=raxes,
            dist_hist_compression=comp, dist_hist_k=k_comp,
            collective_key=comp_key)

    def all_bad(flag):
        """Shard-local non-finite flag -> mesh-global (every shard must take
        the same skip decision or the forests desync)."""
        if flag is None:
            return None
        b = flag.astype(jnp.float32)
        for ax in raxes:
            b = jax.lax.pmax(b, ax)
        return jax.lax.pmax(b, model_axis) > 0

    def local_step(F_l, codes_l, Y_l, key):
        n_loc, d_loc = F_l.shape
        m = codes_l.shape[1]
        d_global = d_loc * tp
        G, Hd = sharded_grad_hess(cfg.loss, F_l, Y_l, model_axis, d_loc)
        G, Hd, bad = GU.guard_grad_hess(G, Hd, cfg.guard_policy,
                                        cfg.guard_clip, cfg.hessian_floor)
        bad = all_bad(bad)

        # Same derivation as boosting._boost_round: k_key drives the sketch;
        # s_key / c_key are burned (SGB/GOSS + colsample are single-device-
        # only) so seeds stay comparable across paths.
        k_key, _s_key, _c_key = jax.random.split(key, 3)
        comp_key = (jax.random.fold_in(key, 7919) if comp == "sketch"
                    else None)

        if feature_shard:
            if m % tp:
                raise ValueError(
                    f"feature_shard=True needs the feature count ({m}) "
                    f"divisible by the model axis ({tp}); pad the feature "
                    "matrix or use feature_shard=False")
            m_loc = m // tp
            f_off = jax.lax.axis_index(model_axis) * m_loc
            codes_h = jax.lax.dynamic_slice_in_dim(codes_l, f_off, m_loc,
                                                   axis=1)
        else:
            f_off = jnp.int32(0)
            codes_h = codes_l

        if cfg.strategy == "single_tree":
            Gk = SK.sketch_sharded(G, method=cfg.sketch_method,
                                   k=cfg.sketch_k, key=k_key,
                                   d_global=d_global, model_axis=model_axis,
                                   data_axes=raxes)
            stats = jnp.concatenate(
                [Gk, jnp.ones((n_loc, 1), jnp.float32)], axis=1)
            # Re-check post-sketch (a projection can overflow on its own),
            # then round: same placement as boosting._boost_round.
            stats, bad = GU.guard_stats(stats, cfg.guard_policy,
                                        cfg.guard_clip, bad)
            bad = all_bad(bad) if cfg.guard_policy in ("skip_round", "clip") \
                else bad
            stats = maybe_bf16(stats)
            skip = (GU.skip_scale(bad, cfg.guard_policy)
                    if cfg.guard_policy == "skip_round" else None)
            if cfg.growth == "leafwise":
                tree, leaf_pos = grow_leafwise(codes_l, stats, G, Hd,
                                               comp_key)
                if skip is not None:
                    tree = tree._replace(value=tree.value * skip,
                                         gain=tree.gain * skip)
                F_new = F_l + cfg.learning_rate * tree.value[leaf_pos]
                return F_new, tree
            heap_feat, heap_thr, heap_gain, node_pos = grow_levelwise(
                codes_l, codes_h, stats, f_off, comp_key)
            value, cover = leaf_pass(node_pos, G, Hd, 2 ** depth)
            if skip is not None:
                value, heap_gain = value * skip, heap_gain * skip
            F_new = F_l + cfg.learning_rate * value[node_pos]
            tree = T.Tree(feat=heap_feat, thr=heap_thr, value=value,
                          gain=heap_gain, cover=cover)
            return F_new, tree

        # one_vs_all: vmap the per-output grower over this shard's output
        # slice; collectives batch across the vmapped axis.
        ones = jnp.ones((n_loc, 1), jnp.float32)

        def grow_one(g_j, h_j):
            stats_j = maybe_bf16(jnp.concatenate([g_j[:, None], ones],
                                                 axis=1))
            if cfg.growth == "leafwise":
                tree, leaf_pos = grow_leafwise(codes_l, stats_j,
                                               g_j[:, None], h_j[:, None],
                                               comp_key)
                return tree, tree.value[leaf_pos, 0]
            heap_feat, heap_thr, heap_gain, node_pos = grow_levelwise(
                codes_l, codes_l, stats_j, f_off, comp_key)
            value, cover = leaf_pass(node_pos, g_j[:, None], h_j[:, None],
                                     2 ** depth)
            tree = T.Tree(feat=heap_feat, thr=heap_thr, value=value,
                          gain=heap_gain, cover=cover)
            return tree, value[node_pos, 0]

        trees, deltas = jax.vmap(grow_one, in_axes=(1, 1))(G, Hd)
        if cfg.guard_policy == "skip_round":
            # one_vs_all stats are plain sanitized-gradient channels (no
            # sketch projection), so the grad/hess flag alone gates the
            # round — mirror boosting._boost_round.
            scale = GU.skip_scale(bad, cfg.guard_policy)
            trees = trees._replace(value=trees.value * scale,
                                   gain=trees.gain * scale)
            deltas = deltas * scale
        F_new = F_l + cfg.learning_rate * deltas.T
        return F_new, trees

    if cfg.strategy == "single_tree":
        val_spec = P(None, model_axis)
        if cfg.growth == "leafwise":
            tree_specs = T.NodeTree(feat=P(), thr=P(), left=P(), right=P(),
                                    value=val_spec, gain=P(), cover=P(),
                                    node_count=P())
        else:
            tree_specs = T.Tree(feat=P(), thr=P(), value=val_spec, gain=P(),
                                cover=P())
    else:
        # Leading per-output axis sharded over the model axis (matches the
        # single-device vmapped layout once gathered).
        mspec = P(model_axis)
        if cfg.growth == "leafwise":
            tree_specs = T.NodeTree(feat=mspec, thr=mspec, left=mspec,
                                    right=mspec, value=mspec, gain=mspec,
                                    cover=mspec, node_count=mspec)
        else:
            tree_specs = T.Tree(feat=mspec, thr=mspec, value=mspec,
                                gain=mspec, cover=mspec)
    step = shard_map(local_step, mesh=mesh,
                     in_specs=(f_spec, row_spec, y_spec, P()),
                     out_specs=(f_spec, tree_specs),
                     check_vma=False)
    return jax.jit(step, donate_argnums=(0,))


def make_distributed_eval(mesh: Mesh, cfg: GBDTConfig, *,
                          row_axes: Tuple[str, ...] = ("data",),
                          model_axis: str = "model"):
    """Jitted sharded loss evaluation ``(F, Y) -> scalar``."""
    cfg.validate(distributed=True)
    row_spec = P(row_axes)
    f_spec = P(row_axes, model_axis)
    y_spec = row_spec if cfg.loss == "multiclass" else f_spec

    def local_eval(F_l, Y_l):
        return sharded_loss_value(cfg.loss, F_l, Y_l, model_axis, row_axes,
                                  F_l.shape[1])

    fn = shard_map(local_eval, mesh=mesh, in_specs=(f_spec, y_spec),
                   out_specs=P(), check_vma=False)
    return jax.jit(fn)


def fit_distributed(cfg: GBDTConfig, mesh: Mesh, codes: jax.Array,
                    Y: jax.Array, *,
                    row_axes: Tuple[str, ...] = ("data",),
                    model_axis: str = "model",
                    feature_shard: bool = False,
                    base_score: Optional[jax.Array] = None,
                    n_rounds: Optional[int] = None,
                    eval_every: int = 0,
                    chaos: Any = None,
                    watchdog: Any = None):
    """Multi-device training driver: ``cfg.n_trees`` distributed rounds.

    ``codes`` is the (n, m) pre-binned feature matrix (see `core.quantize`)
    and ``Y`` the targets; ``cfg.n_outputs`` must be set (the sharded step
    cannot infer d from labels).  Rounds run through
    `make_distributed_boost_step` with the same key schedule as the
    single-device python loop (``key = PRNGKey(seed)``; ``key, sub =
    split(key)`` per round), so a fixed seed reproduces the single-device
    forest — the property the parity suite pins down.

    The round loop itself is a `runtime.fault.RestartableLoop`: with
    ``cfg.save_every > 0`` it writes format-v4 boost checkpoints (the same
    `io.checkpoint.save_boost_checkpoint` steps `SketchBoost.fit` writes, so
    every step doubles as a serving checkpoint) into ``cfg.ckpt_dir``, and
    ``cfg.resume_from`` restores one and continues — *including onto a
    different mesh* than wrote it: checkpoints are mesh-agnostic host
    arrays, laid out on THIS mesh via `elastic.remesh` (the elastic-restart
    path after a host loss).  ``chaos`` takes `runtime.chaos` injections
    (kill / drop-host / NaN-at-row / delay-shard); ``watchdog`` an optional
    `fault.StragglerWatchdog` to observe per-round times (DelayShard's
    virtual seconds included).

    Returns ``(F, forest, history)``: the final raw scores (n, d), the
    stacked training-side forest (`tree.Forest` level-wise /
    `tree.NodeTree` leaf-wise, one leading round axis — same layout
    `SketchBoost.fit` produces, consumable by `forest.pack_forest`), and a
    list of ``{"round", "train_loss"}`` records (every ``eval_every``
    rounds; empty when 0).
    """
    from repro.runtime import chaos as CH
    from repro.runtime import elastic as E
    from repro.runtime import fault as FT

    if cfg.n_outputs < 1:
        raise ValueError(
            "fit_distributed needs cfg.n_outputs set explicitly (the "
            "sharded step shards the output axis before seeing labels); "
            "e.g. dataclasses.replace(cfg, n_outputs=d)")
    d = cfg.n_outputs
    n = codes.shape[0]
    run_cfg = cfg.strip_io()        # ckpt knobs stay out of jit cache keys
    step = make_distributed_boost_step(mesh, run_cfg, row_axes=row_axes,
                                       model_axis=model_axis,
                                       feature_shard=feature_shard)
    evaluate = (make_distributed_eval(mesh, run_cfg, row_axes=row_axes,
                                      model_axis=model_axis)
                if eval_every else None)
    base = (jnp.zeros((d,), jnp.float32) if base_score is None
            else jnp.asarray(base_score, jnp.float32))
    F0 = jnp.broadcast_to(base, (n, d)).astype(jnp.float32)
    rounds = int(n_rounds) if n_rounds else cfg.n_trees
    f_sharding = NamedSharding(mesh, P(row_axes, model_axis))
    chaos = CH.as_chaos_list(chaos)
    history: List[Dict[str, Any]] = []
    # Chaos may poison Y mid-run (persistently); box it so step_fn's closure
    # carries the mutation forward.
    Y_box = [jnp.asarray(Y)]

    save_fn = None
    if cfg.save_every > 0 and cfg.ckpt_dir:
        from repro.io import checkpoint as CK

        def save_fn(step_idx, state):
            forest = _as_forest(_concat_chunks(state["trees"]))
            packed = FO.pack_forest(
                forest, base, cfg.learning_rate, strategy=cfg.strategy,
                max_depth=cfg.depth if cfg.growth == "leafwise" else None)
            CK.save_boost_checkpoint(
                cfg.ckpt_dir, round_done=step_idx + 1, packed=packed,
                quantizer=None, trees=forest, F=state["F"], Fv=None,
                key=state["key"], history=history, best_loss=float("inf"),
                best_round=-1, cfg_meta=dict(_resume_cfg_snapshot(cfg),
                                             loss=cfg.loss),
                keep_n=cfg.ckpt_keep)

    restore_fn = None
    if cfg.resume_from:
        from repro.io import checkpoint as CK

        def restore_fn():
            st = CK.load_boost_checkpoint(cfg.resume_from)
            _check_resume_compat(cfg, st)
            if tuple(st.F.shape) != (n, d):
                raise ValueError(
                    f"resume_from checkpoint holds training scores of "
                    f"shape {tuple(st.F.shape)} but codes/cfg give "
                    f"({n}, {d}); resume must use the same training data")
            prefix = st.trees
            if isinstance(prefix, T.Forest):
                prefix = T.Tree(**prefix._asdict())
            history.extend(st.history)
            # Elastic restart: the step's host arrays are laid out on THIS
            # mesh — possibly a survivor subset of the mesh that wrote it.
            F = E.remesh(jnp.asarray(st.F, jnp.float32), f_sharding)
            return {"F": F, "key": st.key, "trees": [prefix]}, st.round

    def step_fn(state, it):
        for c in chaos:
            mutate = getattr(c, "mutate_targets", None)
            if mutate is not None:
                Y_box[0] = mutate(Y_box[0], it)
        key, sub = jax.random.split(state["key"])
        F, tree = step(state["F"], codes, Y_box[0], sub)
        if cfg.guard_policy == "raise":
            GU.check_scores_host(F, it)
        metrics: Dict[str, Any] = {}
        if eval_every and it % eval_every == 0:
            tl = float(evaluate(F, Y_box[0]))
            history.append({"round": it, "train_loss": tl})
            metrics["train_loss"] = tl
        # Rounds append as 1-round stacked chunks: concat(chunks) is bitwise
        # the stack the pre-fault-tolerance loop built.
        trees = state["trees"] + [jax.tree.map(lambda x: x[None], tree)]
        return {"F": F, "key": key, "trees": trees}, metrics

    loop = FT.RestartableLoop(
        "", step_fn, save_every=cfg.save_every, keep_n=cfg.ckpt_keep,
        async_save=False, save_fn=save_fn, restore_fn=restore_fn,
        chaos=chaos, watchdog=watchdog)
    state, _done = loop.run({"F": F0, "key": jax.random.key(cfg.seed),
                             "trees": []}, None, rounds)
    stacked = _concat_chunks(state["trees"])
    return state["F"], _as_forest(stacked), history


def gbdt_input_specs(n: int, m: int, d: int, mesh: Mesh, cfg: GBDTConfig, *,
                     row_axes=("data",), model_axis="model"):
    """ShapeDtypeStruct stand-ins + shardings for the GBDT dry-run cell."""
    f_sh = NamedSharding(mesh, P(row_axes, model_axis))
    row_sh = NamedSharding(mesh, P(row_axes))
    if cfg.loss == "multiclass":
        y = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=row_sh)
    else:
        y = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=f_sh)
    return dict(
        F=jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=f_sh),
        codes=jax.ShapeDtypeStruct((n, m), jnp.uint8, sharding=row_sh),
        Y=y,
        # PRNG keys are tiny; the dry-run passes a concrete jax.random.key(0).
        key=None,
    )
