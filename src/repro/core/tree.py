"""Oblivious-free multivariate decision trees: depth-wise growth + heap layout.

A tree of depth D is a perfect binary heap: internal nodes ``0 .. 2^D-2`` (level
``l`` occupies ``[2^l - 1, 2^(l+1) - 1)``), leaves ``0 .. 2^D - 1``.  Samples that
reach a no-split node are routed left, so pass-through nodes behave as leaves.

Growth follows the paper exactly:
  1. split search uses the *sketched* statistics (``stats`` = [G_k | 1]),
  2. leaf values use the *full* gradients/Hessians (eq. (3)):
     ``v_j = - sum_i g_i / (sum_i h_i + lambda)``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import histogram as H
from repro.core import split as S
from repro.kernels import ref
from repro.runtime import tracing as TR


class Tree(NamedTuple):
    feat: jax.Array    # (2^D - 1,) int32
    thr: jax.Array     # (2^D - 1,) int32 — go left if code <= thr
    value: jax.Array   # (2^D, d) float32 leaf values
    gain: jax.Array    # (2^D - 1,) float32 diagnostics
    cover: Optional[jax.Array] = None  # (2^D,) weighted train rows per leaf

    @property
    def depth(self) -> int:
        return (self.feat.shape[0] + 1).bit_length() - 1


def route_bits(codes: jax.Array, node_pos: jax.Array, feat: jax.Array,
               thr: jax.Array) -> jax.Array:
    """Per-sample routing bit at the current level: ``[code > thr]``."""
    n = codes.shape[0]
    f = feat[node_pos]                                    # (n,)
    code = codes[jnp.arange(n), f].astype(jnp.int32)
    return (code > thr[node_pos]).astype(jnp.int32)


def route_level(codes: jax.Array, node_pos: jax.Array, feat: jax.Array,
                thr: jax.Array) -> jax.Array:
    """Advance every sample one level: ``pos <- 2*pos + [code > thr]``."""
    return node_pos * 2 + route_bits(codes, node_pos, feat, thr)


@functools.partial(
    jax.jit,
    static_argnames=("depth", "n_bins", "use_kernel", "hist_engine",
                     "hist_dtype"))
def grow_tree(codes: jax.Array, stats: jax.Array, G: jax.Array, H_diag: jax.Array,
              *, depth: int, n_bins: int, lam: float,
              min_data_in_leaf: float = 1.0, min_gain: float = 0.0,
              feature_mask: Optional[jax.Array] = None,
              use_kernel=False, hist_engine="auto",
              hist_dtype: str = "float32"):
    """Grow one multivariate tree (single-device path).

    Args:
      codes:   (n, m) uint8 binned features.
      stats:   (n, k+1) sketched gradient stats + count channel (count channel may
               carry SGB/GOSS sample weights).
      G, H_diag: (n, d) full gradients / diagonal Hessians for the leaf pass.
      use_kernel: bool or kernel-mode string (see `histogram.resolve_kernel_mode`).
               Kernel modes run the fused Pallas histogram + split-scan pair per
               level; the jnp mode builds histograms with segment-sum and scans
               them with `split.split_scores` / `split.best_splits`.
      hist_engine: histogram engine (see `histogram.resolve_hist_engine`):
               ``"auto"``/``"subtract"`` carries a node-sorted row partition
               (`histogram.LevelState`) plus the previous level's histograms
               through the level loop, builds only the smaller child of each
               parent and derives the sibling by subtraction; ``"partition"``
               partitions without subtraction; ``"direct"`` is the legacy
               full-rebuild path.
      hist_dtype: MXU input dtype of the partitioned tiles kernel
               (``"float32"`` | ``"bfloat16"``; kernel modes only — the jnp
               path ignores it, which `GBDTConfig.validate` guards against).
    Returns:
      (Tree, leaf_pos) where leaf_pos is the (n,) leaf index of each sample.
    """
    n, m = codes.shape
    mode = H.resolve_kernel_mode(use_kernel)
    engine = H.resolve_hist_engine(hist_engine)
    lam = jnp.float32(lam)
    min_data = jnp.float32(min_data_in_leaf)
    min_gain_ = jnp.float32(min_gain)

    heap_feat = jnp.zeros((2 ** depth - 1,), jnp.int32)
    heap_thr = jnp.full((2 ** depth - 1,), n_bins - 1, jnp.int32)
    heap_gain = jnp.zeros((2 ** depth - 1,), jnp.float32)

    node_pos = jnp.zeros((n,), jnp.int32)
    state = H.init_level_state(n) if engine != "direct" else None
    prev_hist = None                       # previous level's histograms
    for lvl in range(depth):
        n_nodes = 2 ** lvl
        subtract = engine == "subtract" and lvl > 0
        if mode != "jnp":
            from repro.kernels import ops as kops
            interp = mode == "interpret"
            with jax.named_scope(TR.HIST):
                if engine == "direct":
                    best_gain, best_idx = kops.histogram_splits(
                        codes, node_pos, stats, lam, min_data, feature_mask,
                        n_nodes=n_nodes, n_bins=n_bins, interpret=interp)
                else:
                    best_gain, best_idx, prev_hist = (
                        kops.histogram_splits_level(
                            codes, stats, state.order, state.counts,
                            prev_hist, lam, min_data, feature_mask,
                            n_nodes=n_nodes, n_bins=n_bins,
                            subtract=subtract, hist_dtype=hist_dtype,
                            interpret=interp))
            with jax.named_scope(TR.ROUTE):
                sp = S.splits_from_flat(best_gain, best_idx, n_bins=n_bins,
                                        min_gain=min_gain_)
        else:
            with jax.named_scope(TR.HIST):
                if engine == "direct":
                    hist = H.build_histograms_jnp(codes, node_pos, stats,
                                                  n_nodes=n_nodes,
                                                  n_bins=n_bins)
                else:
                    hist = H.build_level_jnp(codes, stats, state, prev_hist,
                                             n_nodes=n_nodes, n_bins=n_bins,
                                             subtract=subtract)
                    prev_hist = hist
                gain = S.split_scores(hist, lam, min_data, feature_mask)
                sp = S.best_splits(gain, min_gain_)
        with jax.named_scope(TR.ROUTE):
            off = n_nodes - 1
            heap_feat = jax.lax.dynamic_update_slice(heap_feat, sp.feat,
                                                     (off,))
            heap_thr = jax.lax.dynamic_update_slice(heap_thr, sp.thr, (off,))
            heap_gain = jax.lax.dynamic_update_slice(heap_gain, sp.gain,
                                                     (off,))
            bits = route_bits(codes, node_pos, sp.feat, sp.thr)
            node_pos = node_pos * 2 + bits
            if state is not None and lvl < depth - 1:
                state = H.advance_level_state(state, bits)

    with jax.named_scope(TR.LEAF):
        sample_w = stats[:, -1:]                          # SGB/GOSS weights
        g_sum, h_sum = H.leaf_sums(node_pos, G * sample_w, H_diag * sample_w,
                                   n_leaves=2 ** depth)
        value = -g_sum / (h_sum + lam)
        # Per-leaf cover (weighted training row counts): the substrate for
        # path-dependent TreeSHAP and cover/split-count importances — packed
        # into the serving format by `forest.pack_forest` so explanation
        # needs no re-scan of training data.
        cover = jax.ops.segment_sum(sample_w[:, 0],
                                    node_pos.astype(jnp.int32),
                                    num_segments=2 ** depth)
    tree = Tree(feat=heap_feat, thr=heap_thr, value=value, gain=heap_gain,
                cover=cover)
    return tree, node_pos


@functools.partial(
    jax.jit,
    static_argnames=("depth", "max_leaves", "n_bins", "use_kernel",
                     "hist_dtype", "psum_axes", "dist_hist_compression",
                     "dist_hist_k"))
def grow_tree_leafwise(codes: jax.Array, stats: jax.Array, G: jax.Array,
                       H_diag: jax.Array, *, depth: int, max_leaves: int,
                       n_bins: int, lam: float,
                       min_data_in_leaf: float = 1.0, min_gain: float = 0.0,
                       feature_mask: Optional[jax.Array] = None,
                       use_kernel=False, hist_dtype: str = "float32",
                       psum_axes: tuple = (),
                       dist_hist_compression: str = "none",
                       dist_hist_k: int = 0,
                       collective_key: Optional[jax.Array] = None):
    """Grow one multivariate tree leaf-wise (LightGBM-style best-first).

    Instead of expanding every node of a level, each step expands the single
    frontier leaf with the highest pending split gain, so a fixed leaf
    budget is spent where the loss says it matters.  The loop is a
    ``jax.lax.scan`` of exactly ``max_leaves - 1`` expansion steps (fixed
    shapes, jit/vmap-compatible); once the frontier is exhausted (no leaf
    has a legal positive-gain split, or every frontier leaf sits at the
    ``depth`` bound) the remaining steps are masked exact no-ops.

    Per expansion the grower reuses the node-partitioned histogram
    machinery (`histogram.NodePartition`, the per-node twin of the level
    engine's `LevelState`): the expanded node's contiguous row segment is
    stably split in place, the histogram of the SMALLER child is built
    directly over a fixed ``n // 2`` row buffer — the tiles Pallas kernel
    (`kernels.ops.node_histogram`) under kernel modes, a per-feature
    segment-sum otherwise — and the sibling is derived by subtraction from
    the parent's cached histogram (every frontier leaf keeps its histogram
    in a ``max_leaves``-slot pool, LightGBM's histogram-pool trick), after
    which both children are scored through the same split-scan used by the
    level engine.

    Node numbering is creation order: root 0, expansion ``t`` appends its
    two children — children always carry larger ids than their parent.
    Returns ``(NodeTree, leaf_pos)`` where ``leaf_pos`` is the (n,) terminal
    node id of each sample.

    Distributed growth (called from inside shard_map by
    `core.distributed.make_distributed_boost_step`): with ``psum_axes``
    non-empty every per-node histogram, row count, and leaf sum is psummed
    over those row axes right after its shard-local build, so every shard
    sees identical (global) split decisions while rows stay sharded.  The
    built-child gather then uses a FULL ``n``-row local buffer — the
    *globally* smaller child can hold more than ``n // 2`` of one shard's
    local rows, and the ``n // 2`` buffer would silently drop the overflow.
    ``dist_hist_compression="sketch"`` routes the histogram psum's gradient
    channels through the JL machinery of `distributed.compression` (count
    channel always exact; ``collective_key`` must then be the same on every
    shard so the projection replicates for free).

    Numerics: for a given set of expanded nodes the built/derived histogram
    chain is the same one the level-wise ``subtract`` engine produces (same
    smaller-child choice, same partition-ordered summation), so with
    ``max_leaves = 2^depth`` and no early frontier exhaustion the splits
    reproduce level-wise growth exactly — asserted by the equivalence
    tests.
    """
    n, m = codes.shape
    c = stats.shape[1]
    mode = H.resolve_kernel_mode(use_kernel)
    sharded = bool(psum_axes)
    if dist_hist_compression == "sketch" and collective_key is None:
        raise ValueError("dist_hist_compression='sketch' needs a "
                         "collective_key (replicated across shards)")
    # Locally-smaller is not globally-smaller: under sharding the built
    # child may own up to ALL of a shard's local rows.
    n_buf = n if sharded else max(n // 2, 1)
    N = 2 * max_leaves - 1
    lam_ = jnp.float32(lam)
    min_data_ = jnp.float32(min_data_in_leaf)
    min_gain_ = jnp.float32(min_gain)
    neg_inf = jnp.float32(-jnp.inf)

    def _psum(x):
        for ax in psum_axes:
            x = jax.lax.psum(x, ax)
        return x

    def reduce_hist(h, key):
        """All-reduce one (m, B, c) node histogram over the row axes."""
        if not sharded:
            return h
        if dist_hist_compression == "sketch":
            from repro.distributed import compression as C
            g, cnt = h[..., :-1], h[..., -1:]
            sk, Pi, shape = C.compress_block(g.reshape(-1, c - 1), key,
                                             dist_hist_k)
            g = C.decompress_block(_psum(sk), Pi, shape).reshape(g.shape)
            return jnp.concatenate([g, _psum(cnt)], axis=-1)
        return _psum(h)

    @jax.named_scope(TR.HIST)
    def build_hist(rows, valid, key=None):
        with jax.named_scope(TR.GATHER):
            codes_g = codes[rows].astype(jnp.int32)
            stats_g = stats[rows].astype(jnp.float32) * valid[:, None]
        if mode != "jnp":
            from repro.kernels import ops as kops
            h = kops.node_histogram(codes_g, stats_g, n_bins=n_bins,
                                    hist_dtype=hist_dtype,
                                    interpret=mode == "interpret")
        else:
            h = H.node_hist_jnp(codes_g, stats_g, n_bins=n_bins)
        return reduce_hist(h, key)

    @jax.named_scope(TR.HIST)
    def score(hists, k: int) -> S.Splits:
        """Best splits of ``k`` stacked (m, B, c) histograms."""
        if mode != "jnp":
            from repro.kernels import ops as kops
            native = hists.transpose(1, 0, 2, 3).reshape(m, k * n_bins, c)
            g, i = kops.split_scan(native, lam_, min_data_, feature_mask,
                                   n_nodes=k, n_bins=n_bins,
                                   interpret=mode == "interpret")
            return S.splits_from_flat(g, i, n_bins=n_bins,
                                      min_gain=min_gain_)
        gains = S.split_scores(hists, lam_, min_data_, feature_mask)
        return S.best_splits(gains, min_gain_)

    ids = jnp.arange(N, dtype=jnp.int32)
    root_key = (jax.random.fold_in(collective_key, 0)
                if dist_hist_compression == "sketch" else None)
    root_hist = build_hist(jnp.arange(n, dtype=jnp.int32),
                           jnp.ones((n,), jnp.float32), root_key)
    sp0 = score(root_hist[None], 1)
    root_gain = jnp.where(sp0.is_leaf[0] | (depth < 1) | (max_leaves < 2),
                          neg_inf, sp0.gain[0])

    carry = dict(
        part=H.init_node_partition(n, N),
        feat=jnp.zeros((N,), jnp.int32),
        thr=jnp.full((N,), n_bins - 1, jnp.int32),
        left=ids, right=ids,                      # all nodes start as leaves
        gain=jnp.zeros((N,), jnp.float32),
        node_depth=jnp.zeros((N,), jnp.int32),
        pend_gain=jnp.full((N,), -jnp.inf).at[0].set(root_gain),
        pend_feat=jnp.zeros((N,), jnp.int32).at[0].set(sp0.feat[0]),
        pend_thr=jnp.zeros((N,), jnp.int32).at[0].set(sp0.thr[0]),
        cache=jnp.zeros((max_leaves, m, n_bins, c),
                        jnp.float32).at[0].set(root_hist),
        slot_of=jnp.zeros((N,), jnp.int32),
        node_count=jnp.int32(1),
    )

    def expand(carry, t):
        s = dict(carry)
        pend_gain = s["pend_gain"]
        p = jnp.argmax(pend_gain).astype(jnp.int32)
        g_p = pend_gain[p]
        do = g_p > min_gain_                      # -inf once exhausted
        f_p, t_p = s["pend_feat"][p], s["pend_thr"][p]
        c1, c2 = s["node_count"], s["node_count"] + 1
        with jax.named_scope(TR.ROUTE):
            go_right = jnp.take(codes, f_p, axis=1).astype(jnp.int32) > t_p
            part = H.split_partition_at(s["part"], p, c1, c2, go_right, do)

        def upd(a, i, v):
            return a.at[i].set(jnp.where(do, v, a[i]))

        s["feat"] = upd(s["feat"], p, f_p)
        s["thr"] = upd(s["thr"], p, t_p)
        s["gain"] = upd(s["gain"], p, g_p)
        s["left"] = upd(s["left"], p, c1)
        s["right"] = upd(s["right"], p, c2)
        d_child = s["node_depth"][p] + 1
        s["node_depth"] = upd(upd(s["node_depth"], c1, d_child), c2, d_child)

        # Build the smaller child directly; derive the sibling from the
        # parent's cached histogram (sibling subtraction, ties -> left).
        # Under sharding the choice uses GLOBAL counts so every shard
        # builds (and derives) the same child even where local counts
        # disagree with the global ordering.
        built_left = _psum(part.counts[c1]) <= _psum(part.counts[c2])
        rows, valid = H.gather_node_rows(
            part, jnp.where(built_left, c1, c2), n_buf)
        exp_key = (jax.random.fold_in(collective_key, t + 1)
                   if dist_hist_compression == "sketch" else None)
        built = build_hist(rows, valid.astype(jnp.float32), exp_key)
        s_p = s["slot_of"][p]
        with jax.named_scope(TR.HIST), jax.named_scope(TR.SUBTRACT):
            sib = s["cache"][s_p] - built
            hist_l = jnp.where(built_left, built, sib)
            hist_r = jnp.where(built_left, sib, built)
        sp = score(jnp.stack([hist_l, hist_r]), 2)

        # Frontier update: children become pending unless illegal (no
        # positive-gain split) or at the depth bound.
        expandable = do & ~sp.is_leaf & (d_child < depth)    # (2,)
        s["pend_gain"] = pend_gain.at[p].set(
            jnp.where(do, neg_inf, pend_gain[p]))
        for j, cj in ((0, c1), (1, c2)):
            s["pend_gain"] = s["pend_gain"].at[cj].set(
                jnp.where(do, jnp.where(expandable[j], sp.gain[j], neg_inf),
                          s["pend_gain"][cj]))
            s["pend_feat"] = upd(s["pend_feat"], cj, sp.feat[j])
            s["pend_thr"] = upd(s["pend_thr"], cj, sp.thr[j])

        # Histogram pool: the left child reuses the parent's slot, the
        # right child takes this expansion's fresh slot t + 1.
        s_new = (t + 1).astype(jnp.int32)
        cache = s["cache"].at[s_p].set(jnp.where(do, hist_l,
                                                 s["cache"][s_p]))
        s["cache"] = cache.at[s_new].set(jnp.where(do, hist_r,
                                                   cache[s_new]))
        s["slot_of"] = upd(upd(s["slot_of"], c1, s_p), c2, s_new)
        s["node_count"] = s["node_count"] + jnp.where(do, 2, 0)
        s["part"] = part
        return s, None

    carry, _ = jax.lax.scan(expand, carry,
                            jnp.arange(max_leaves - 1, dtype=jnp.int32))
    part = carry["part"]
    left, right = carry["left"], carry["right"]

    # Terminal node of every row, then the exact leaf pass (eq. (3)) on the
    # full gradients — identical per-leaf summation order to the level-wise
    # grower (original row order within each leaf).
    with jax.named_scope(TR.LEAF):
        leaf_pos = jnp.zeros((n,), jnp.int32).at[part.order].set(
            part.node_perm)
        sample_w = stats[:, -1:]
        g_sum, h_sum = H.leaf_sums(leaf_pos, G * sample_w,
                                   H_diag * sample_w, n_leaves=N)
        g_sum, h_sum = _psum(g_sum), _psum(h_sum)  # exact: never sketched
        is_term = left == ids
        value = jnp.where(is_term[:, None], -g_sum / (h_sum + lam_), 0.0)

        # Node covers bottom-up: children have larger ids, so one reverse
        # sweep makes every internal cover the exact sum of its children
        # (the invariant TreeSHAP's zero-fractions rely on).
        cover_leaf = _psum(jax.ops.segment_sum(sample_w[:, 0],
                                               leaf_pos.astype(jnp.int32),
                                               num_segments=N))

        def up(i, cov):
            j = N - 1 - i
            summed = cov[left[j]] + cov[right[j]]
            return cov.at[j].set(jnp.where(left[j] != j, summed, cov[j]))

        cover = jax.lax.fori_loop(0, N, up, cover_leaf)
    tree = NodeTree(feat=carry["feat"], thr=carry["thr"], left=left,
                    right=right, value=value, gain=carry["gain"],
                    cover=cover, node_count=carry["node_count"])
    return tree, leaf_pos


@functools.partial(jax.jit, static_argnames=("depth",))
def tree_leaf_index(feat: jax.Array, thr: jax.Array, codes: jax.Array,
                    *, depth: int) -> jax.Array:
    """Vectorized heap walk: (n, m) codes -> (n,) leaf index."""
    n = codes.shape[0]
    pos = jnp.zeros((n,), jnp.int32)
    for lvl in range(depth):
        heap = pos + (2 ** lvl - 1)
        f = feat[heap]
        code = codes[jnp.arange(n), f].astype(jnp.int32)
        pos = pos * 2 + (code > thr[heap]).astype(jnp.int32)
    return pos


def predict_tree(tree: Tree, codes: jax.Array) -> jax.Array:
    """(n, m) codes -> (n, d) tree response."""
    pos = tree_leaf_index(tree.feat, tree.thr, codes, depth=tree.depth)
    return tree.value[pos]


class NodeTree(NamedTuple):
    """Sparse-topology tree (or, with a leading ``T`` axis, a stacked forest).

    The node-list twin of the heap `Tree`: a unified node id space of static
    size ``N`` with explicit child pointers, the training-side container the
    leaf-wise (best-first) grower emits and `core.forest.pack_forest`
    consumes directly.  Terminal nodes self-loop (``left[i] == right[i] ==
    i``); slots at and beyond ``node_count`` are inert self-loop leaves with
    zero value, so a fixed-bound pointer walk is exact for any topology.
    Leaf-wise trees number nodes in creation order (root 0; expansion ``t``
    appends children ``2t+1``/``2t+2``-at-the-latest), so children always
    carry larger ids than their parent — which is what lets covers propagate
    bottom-up in one reverse sweep.
    """
    feat: jax.Array        # (N,) int32 split feature (unused on leaves)
    thr: jax.Array         # (N,) int32 — go left if code <= thr
    left: jax.Array        # (N,) int32 child pointers; self-loop on leaves
    right: jax.Array       # (N,) int32
    value: jax.Array       # (N, d) float32 leaf values (0 on internal nodes)
    gain: jax.Array        # (N,) float32 split gains (0 on leaves)
    cover: jax.Array       # (N,) float32 weighted train rows through node
    node_count: jax.Array  # () int32 nodes actually used (<= N)

    @property
    def n_nodes(self) -> int:
        return self.feat.shape[-1]

    @property
    def n_trees(self) -> int:
        """Leading-axis length when stacked as a forest."""
        return self.feat.shape[0]


def heap_to_node_arrays(feat: jax.Array, thr: jax.Array, value: jax.Array):
    """Heap-layout tree buffers -> sparse node-list pointer arrays.

    Maps the perfect heap onto the unified *global* node numbering (internal
    nodes keep ids ``0 .. 2^D - 2``, leaf ``j`` becomes node ``2^D - 1 + j``)
    with explicit pointers ``left = 2i + 1`` / ``right = 2i + 2`` and
    self-loops on the leaves.  Works on any leading batch axes: ``feat``/
    ``thr`` are ``(..., 2^D - 1)`` and ``value`` is ``(..., 2^D, w)``.
    Returns ``(feat, thr, left, right, leaf)`` with node axis ``2^(D+1)-1``.
    """
    h = feat.shape[-1]
    n_leaves = h + 1
    n_nodes = h + n_leaves
    ids = jnp.arange(n_nodes, dtype=jnp.int32)
    internal_left = 2 * jnp.arange(h, dtype=jnp.int32) + 1
    left = jnp.concatenate([internal_left, ids[h:]])
    right = jnp.concatenate([internal_left + 1, ids[h:]])
    batch = feat.shape[:-1]
    zeros_i = jnp.zeros(batch + (n_leaves,), feat.dtype)
    feat_n = jnp.concatenate([feat, zeros_i], axis=-1)
    thr_n = jnp.concatenate([thr, zeros_i.astype(thr.dtype)], axis=-1)
    leaf_n = jnp.concatenate(
        [jnp.zeros(batch + (h,) + value.shape[-1:], value.dtype), value],
        axis=-2)
    left_b = jnp.broadcast_to(left, batch + (n_nodes,))
    right_b = jnp.broadcast_to(right, batch + (n_nodes,))
    return feat_n, thr_n, left_b, right_b, leaf_n


class Forest(NamedTuple):
    """Stacked ensemble of T trees (all arrays carry a leading T axis).

    This is the *training-side* container (what the scan loop stacks).  For
    inference, `core.forest.pack_forest` converts it into a `PackedForest`
    whose compiled traversal paths — including the Pallas kernel — replace
    the per-tree walk below; `predict_forest` is retained as the
    bit-parity reference those paths are tested against.
    """
    feat: jax.Array     # (T, 2^D - 1)
    thr: jax.Array      # (T, 2^D - 1)
    value: jax.Array    # (T, 2^D, d)
    gain: Optional[jax.Array] = None   # (T, 2^D - 1) split gains
    cover: Optional[jax.Array] = None  # (T, 2^D) weighted leaf covers

    @property
    def n_trees(self) -> int:
        return self.feat.shape[0]

    @property
    def depth(self) -> int:
        return (self.feat.shape[1] + 1).bit_length() - 1


@functools.partial(jax.jit, static_argnames=("depth",))
def _forest_apply(feat, thr, value_s, codes, base, *, depth: int):
    def body(acc, tree_arrays):
        f, t, v = tree_arrays
        pos = tree_leaf_index(f, t, codes, depth=depth)
        return acc + v[pos], None

    n = codes.shape[0]
    init = jnp.broadcast_to(base, (n, value_s.shape[-1])).astype(jnp.float32)
    out, _ = jax.lax.scan(body, init, (feat, thr, value_s))
    return out


def predict_forest(forest: Forest, codes: jax.Array, lr: float,
                   base_score: jax.Array) -> jax.Array:
    """Raw ensemble scores F(x) = base + sum_t (lr * f_t)(x), with the
    leaves scaled once up front (`kernels.ref.scaled_leaves`)."""
    return _forest_apply(forest.feat, forest.thr,
                         ref.scaled_leaves(lr, forest.value), codes,
                         base_score, depth=forest.depth)
