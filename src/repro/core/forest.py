"""PackedForest: sparse-topology SoA ensemble format + compiled inference.

Training (`core/boosting.py`) produces scan-stacked per-tree buffers — heap
trees from the level-wise grower, node-list trees from the leaf-wise
(best-first) grower — and this module canonicalizes BOTH into a single
serving-ready structure-of-arrays with *explicit topology*: a unified node
id space per tree with ``left``/``right`` child pointers and a per-tree
``node_count``, the same packed node lists GPU GBDT systems traverse
(XGBoost-GPU, Mitchell et al. 2018).  Every inference entry point runs on
top of it:

  * `forest_apply`       — one fused "add these trees to these scores" op,
                           dispatched to the Pallas pointer-chasing kernel
                           (`kernels/predict_kernel.py`) or its gather-based
                           jnp reference under the same ``use_kernel`` modes
                           as the training kernels;
  * `predict_raw`        — jit'd, chunk-streamed full-forest scoring (the
                           serving hot path);
  * `predict_staged`     — cumulative per-round scores in one compiled scan
                           (model selection / eval curves);
  * `slice_rounds`       — O(1) truncation to ``best_iteration``.

Layout
------
All arrays carry a leading ``T`` (tree) axis over a node axis of static size
``N`` (``2^(D+1) - 1`` for canonicalized depth-``D`` heaps, ``2 *
max_leaves - 1`` for leaf-wise trees):

  feat, thr   (T, N) int32          split feature / threshold per node (go
                                    left when ``code <= thr``; unused on
                                    terminal nodes)
  left, right (T, N) int32          explicit child pointers in the unified
                                    numbering.  Terminal nodes self-loop
                                    (``left[i] == right[i] == i``), so a
                                    fixed ``depth``-bound walk is exact for
                                    any topology; node slots at and beyond
                                    ``node_count`` are inert self-loop
                                    leaves no real pointer reaches.
  leaf        (T, N, w) float32     node-indexed multioutput leaf blocks
                                    (zero on internal nodes).  ``w`` is the
                                    *leaf width*: the full output dim ``d``
                                    for ``single_tree`` (leaf values always
                                    use the full gradients, eq. (3) — only
                                    the split search is sketched to k), or 1
                                    for ``one_vs_all`` univariate trees.
  out_col     (T,) int32            starting output column of each tree's
                                    leaf block (0 when ``w == d``).
  base        (d,) float32          constant base score.
  lr          () float32            learning rate.
  cover       (T, N) float32        weighted training row counts per node,
                                    packed at fit time so path-dependent
                                    TreeSHAP and cover/split importances
                                    (`repro.explain`) never re-scan training
                                    data.  ``None`` for forests packed from
                                    cover-less buffers (pre-v2 checkpoints).
  gain        (T, N) float32        split gains (0 on terminal/pass-through
                                    nodes); ``None`` when unavailable.
  node_count  (T,) int32            nodes actually used per tree.
  depth       int (static)          walk bound: the maximum root-to-leaf
                                    depth over all trees.  A plain Python
                                    int — it parameterizes compiled loop
                                    lengths, so it rides the manifest (not
                                    the array store) through checkpoints.

Heap canonicalization preserves the old *global* node numbering (internal
``0 .. 2^D - 2``, leaf ``j`` at ``2^D - 1 + j``) and walks/leaf gathers
perform the identical float arithmetic, so predictions and SHAP values are
bit-identical to the former implicit-heap engine — asserted by the parity
tests.  All array fields form a flat pytree, so the structure checkpoints
through `io.checkpoint` (format v3; v1/v2 heap checkpoints load through the
heap->pointer converter) and crosses jit boundaries as plain buffers.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import histogram as H
from repro.core import tree as T


class PackedForest(NamedTuple):
    feat: jax.Array      # (T, N) int32
    thr: jax.Array       # (T, N) int32
    left: jax.Array      # (T, N) int32 child pointers (self-loop on leaves)
    right: jax.Array     # (T, N) int32
    leaf: jax.Array      # (T, N, w) float32 node-indexed leaf blocks
    out_col: jax.Array   # (T,) int32
    base: jax.Array      # (d,) float32
    lr: jax.Array        # () float32
    cover: Optional[jax.Array] = None  # (T, N) float32 node covers
    gain: Optional[jax.Array] = None   # (T, N) float32 split gains
    node_count: Optional[jax.Array] = None  # (T,) int32 used nodes
    depth: int = 0       # static walk bound (max root-to-leaf depth)

    @property
    def n_trees(self) -> int:
        return self.feat.shape[0]

    @property
    def n_nodes(self) -> int:
        """Static node-axis size N (>= node_count everywhere)."""
        return self.feat.shape[1]

    @property
    def leaf_width(self) -> int:
        return self.leaf.shape[2]

    @property
    def n_outputs(self) -> int:
        return self.base.shape[0]

    @property
    def trees_per_round(self) -> int:
        """1 for single_tree (full-width leaves), d for one_vs_all."""
        return 1 if self.leaf_width == self.n_outputs else self.n_outputs

    @property
    def n_rounds(self) -> int:
        return self.n_trees // self.trees_per_round

    @property
    def is_heap(self) -> bool:
        """Whether EVERY tree is a canonicalized perfect heap (host-side
        check on concrete pointer arrays — all trees, both pointer tensors:
        a creation-order leaf-wise tree can coincide with the heap pattern
        on one tensor of one tree, so a sampled check would mis-decode)."""
        n = self.n_nodes
        d = (n + 1).bit_length() - 2
        if n != 2 ** (d + 1) - 1:
            return False
        h = 2 ** d - 1
        expect_l = np.concatenate([2 * np.arange(h) + 1, np.arange(h, n)])
        if not np.array_equal(np.asarray(self.left),
                              np.broadcast_to(expect_l, self.left.shape)):
            return False
        expect_r = np.concatenate([2 * np.arange(h) + 2, np.arange(h, n)])
        if not np.array_equal(np.asarray(self.right),
                              np.broadcast_to(expect_r, self.right.shape)):
            return False
        return (self.node_count is None
                or bool(np.all(np.asarray(self.node_count) == n)))


def _heap_cover(leaf_cover: jax.Array) -> jax.Array:
    """(T, 2^D) leaf covers -> (T, 2^(D+1) - 1) full node covers.

    Internal covers are the sums of their leaf descendants (levels built
    bottom-up by pairwise folding), concatenated in global node order:
    root first, leaves last — so ``cover[:, i]`` indexes node ``i`` directly.
    """
    levels = [leaf_cover.astype(jnp.float32)]
    while levels[0].shape[1] > 1:
        top = levels[0]
        levels.insert(0, top[:, 0::2] + top[:, 1::2])
    return jnp.concatenate(levels, axis=1)


def _pointer_max_depth(left, right) -> int:
    """Max root-to-leaf depth from concrete pointer arrays (host-side).

    Both producers (heap canonicalization, the creation-order leaf-wise
    grower) emit children with larger ids than their parent, so one forward
    sweep over node ids computes every node's depth.
    """
    left = np.asarray(left)
    right = np.asarray(right)
    n_trees, n = left.shape
    d = np.zeros((n_trees, n), np.int32)
    rows = np.arange(n_trees)
    for i in range(n):
        internal = left[:, i] != i
        r = rows[internal]
        d[r, left[internal, i]] = d[r, i] + 1
        d[r, right[internal, i]] = d[r, i] + 1
    return int(d.max()) if n else 0


def _pack_heap(forest: T.Forest, strategy: str):
    """Heap training buffers -> node-list arrays (strategy folded in)."""
    gain, leaf_cover = forest.gain, forest.cover
    if strategy == "single_tree":
        feat, thr, value = forest.feat, forest.thr, forest.value
        out_col = jnp.zeros((feat.shape[0],), jnp.int32)
    elif strategy == "one_vs_all":
        n_rounds, d = forest.feat.shape[0], forest.feat.shape[1]
        feat = forest.feat.reshape(n_rounds * d, -1)
        thr = forest.thr.reshape(n_rounds * d, -1)
        value = forest.value.reshape(n_rounds * d, forest.value.shape[2], -1)
        out_col = jnp.tile(jnp.arange(d, dtype=jnp.int32), n_rounds)
        if gain is not None:
            gain = gain.reshape(n_rounds * d, -1)
        if leaf_cover is not None:
            leaf_cover = leaf_cover.reshape(n_rounds * d, -1)
    else:
        raise ValueError(f"unknown strategy {strategy!r}")
    h = feat.shape[1]
    n_leaves = h + 1
    feat_n, thr_n, left, right, leaf = T.heap_to_node_arrays(
        feat.astype(jnp.int32), thr.astype(jnp.int32),
        value.astype(jnp.float32))
    cover = None if leaf_cover is None else _heap_cover(leaf_cover)
    gain_n = (None if gain is None else jnp.concatenate(
        [gain.astype(jnp.float32),
         jnp.zeros((gain.shape[0], n_leaves), jnp.float32)], axis=1))
    node_count = jnp.full((feat.shape[0],), h + n_leaves, jnp.int32)
    depth = n_leaves.bit_length() - 1
    return (feat_n, thr_n, left, right, leaf, out_col, cover, gain_n,
            node_count, depth)


def _pack_nodes(forest: T.NodeTree, strategy: str):
    """Stacked `NodeTree` buffers -> node-list arrays (strategy folded in)."""
    feat, thr, left, right = forest.feat, forest.thr, forest.left, forest.right
    value, gain, cover = forest.value, forest.gain, forest.cover
    node_count = forest.node_count
    if strategy == "single_tree":
        out_col = jnp.zeros((feat.shape[0],), jnp.int32)
    elif strategy == "one_vs_all":
        n_rounds, d, n = feat.shape

        def fold(x):
            return None if x is None else x.reshape((n_rounds * d,)
                                                    + x.shape[2:])

        feat, thr, left, right = map(fold, (feat, thr, left, right))
        value, gain, cover = map(fold, (value, gain, cover))
        node_count = fold(node_count)
        out_col = jnp.tile(jnp.arange(d, dtype=jnp.int32), n_rounds)
    else:
        raise ValueError(f"unknown strategy {strategy!r}")
    return (feat.astype(jnp.int32), thr.astype(jnp.int32),
            left.astype(jnp.int32), right.astype(jnp.int32),
            value.astype(jnp.float32), out_col,
            None if cover is None else cover.astype(jnp.float32),
            None if gain is None else gain.astype(jnp.float32),
            node_count.astype(jnp.int32), None)


def pack_forest(forest: Union[T.Forest, T.NodeTree], base_score: jax.Array,
                learning_rate, *, strategy: str = "single_tree",
                max_depth: Optional[int] = None) -> PackedForest:
    """Canonicalize scan-stacked training buffers into a `PackedForest`.

    Accepts BOTH tree topologies: heap `tree.Forest` buffers (level-wise
    grower) are mapped onto the global node numbering with explicit heap
    pointers; stacked `tree.NodeTree` buffers (leaf-wise grower) pack
    verbatim.  ``single_tree`` buffers arrive as ``(T, ...)``;
    ``one_vs_all`` buffers carry an extra per-output axis ``(T, d, ...)``
    which is folded into the tree axis in round-major order (round 0 output
    0, round 0 output 1, ...), so `slice_rounds` and the per-column
    accumulation order both match the training loop exactly.  ``max_depth``
    overrides the walk bound (the leaf-wise trainer passes its configured
    depth limit); by default it is derived from the heap shape or, for
    node-list buffers, from a host-side pointer sweep.
    """
    base = jnp.asarray(base_score, jnp.float32).reshape(-1)
    if isinstance(forest, T.NodeTree):
        (feat, thr, left, right, leaf, out_col, cover, gain, node_count,
         depth) = _pack_nodes(forest, strategy)
    else:
        (feat, thr, left, right, leaf, out_col, cover, gain, node_count,
         depth) = _pack_heap(forest, strategy)
    if max_depth is not None:
        depth = max_depth
    elif depth is None:
        depth = _pointer_max_depth(left, right)
    return PackedForest(feat=feat, thr=thr, left=left, right=right,
                        leaf=leaf, out_col=out_col, base=base,
                        lr=jnp.float32(learning_rate), cover=cover,
                        gain=gain, node_count=node_count, depth=int(depth))


def heap_packed_to_pointer(feat, thr, leaf, out_col, base, lr, cover=None,
                           gain=None) -> PackedForest:
    """Implicit-heap packed arrays (formats v1/v2) -> pointer `PackedForest`.

    ``feat``/``thr`` are ``(T, 2^D - 1)`` internal-node arrays, ``leaf`` is
    the ``(T, 2^D, w)`` leaf-indexed block tensor, and ``cover`` (when
    present) is already in global node order — the numbering this format
    preserves.  Used by `io.checkpoint.load_forest_checkpoint` to upgrade
    old checkpoints in memory; predictions are bit-identical.
    """
    feat = jnp.asarray(feat, jnp.int32)
    thr = jnp.asarray(thr, jnp.int32)
    leaf = jnp.asarray(leaf, jnp.float32)
    h = feat.shape[1]
    n_leaves = h + 1
    feat_n, thr_n, left, right, leaf_n = T.heap_to_node_arrays(feat, thr,
                                                               leaf)
    gain_n = (None if gain is None else jnp.concatenate(
        [jnp.asarray(gain, jnp.float32),
         jnp.zeros((feat.shape[0], n_leaves), jnp.float32)], axis=1))
    return PackedForest(
        feat=feat_n, thr=thr_n, left=left, right=right, leaf=leaf_n,
        out_col=jnp.asarray(out_col, jnp.int32),
        base=jnp.asarray(base, jnp.float32).reshape(-1),
        lr=jnp.asarray(lr, jnp.float32).reshape(()),
        cover=None if cover is None else jnp.asarray(cover, jnp.float32),
        gain=gain_n,
        node_count=jnp.full((feat.shape[0],), h + n_leaves, jnp.int32),
        depth=n_leaves.bit_length() - 1)


def unpack_forest(pf: PackedForest):
    """Inverse of `pack_forest`: ``(forest, strategy)`` round trip.

    Heap-canonical forests unpack back into the training-side `tree.Forest`
    (heap buffers, leaf covers bit-exact — the leaf block of ``pf.cover`` is
    a verbatim copy of the training buffers; only internal covers are
    derived).  Sparse-topology forests unpack into a stacked
    `tree.NodeTree`."""
    one_vs_all = pf.leaf_width != pf.n_outputs
    d = pf.n_outputs
    if pf.is_heap:
        h = (pf.n_nodes - 1) // 2
        feat, thr = pf.feat[:, :h], pf.thr[:, :h]
        value = pf.leaf[:, h:]
        gain = None if pf.gain is None else pf.gain[:, :h]
        leaf_cover = None if pf.cover is None else pf.cover[:, h:]
        if not one_vs_all:
            return T.Forest(feat=feat, thr=thr, value=value, gain=gain,
                            cover=leaf_cover), "single_tree"
        n_rounds = pf.n_trees // d
        return T.Forest(
            feat=feat.reshape(n_rounds, d, -1),
            thr=thr.reshape(n_rounds, d, -1),
            value=value.reshape(n_rounds, d, value.shape[1], 1),
            gain=None if gain is None else gain.reshape(n_rounds, d, -1),
            cover=None if leaf_cover is None
            else leaf_cover.reshape(n_rounds, d, -1)), "one_vs_all"
    fields = dict(feat=pf.feat, thr=pf.thr, left=pf.left, right=pf.right,
                  value=pf.leaf, gain=pf.gain, cover=pf.cover,
                  node_count=pf.node_count)
    if one_vs_all:
        n_rounds = pf.n_trees // d

        def unfold(x):
            return None if x is None else x.reshape((n_rounds, d)
                                                    + x.shape[1:])

        fields = {k: unfold(v) for k, v in fields.items()}
        return T.NodeTree(**fields), "one_vs_all"
    return T.NodeTree(**fields), "single_tree"


# Fields carrying a leading tree axis — the slicing surface shared by
# `PackedForest` and `core.quantize.QuantizedForest` (which adds
# ``leaf_scale``).  ``base``/``lr``/``depth`` are per-forest and excluded.
_TREE_AXIS_FIELDS = ("feat", "thr", "left", "right", "leaf", "leaf_scale",
                     "out_col", "cover", "gain", "node_count")


def slice_rounds(pf, n_rounds: int, *, tighten_depth: bool = False):
    """First ``n_rounds`` boosting rounds (e.g. ``best_iteration``) — a pure
    slice of the tree axis, no recomputation.

    Works on any forest variant (fp32 `PackedForest`, quantized, pruned,
    compacted): every field with a leading tree axis is sliced — including
    a quantized forest's ``leaf_scale`` — rather than assuming the dense
    fp32 field set, so the serving overload fallback
    (`training.serve_lib.ForestServer`) composes with compression.  The
    static walk bound ``depth`` is a forest-wide maximum and stays valid
    for any prefix; ``tighten_depth=True`` recomputes it from the sliced
    pointers (host-side sweep) — a cheaper walk for shallow prefixes at the
    cost of a fresh compile shape.
    """
    t = n_rounds * pf.trees_per_round
    upd = {k: v[:t] for k, v in pf._asdict().items()
           if k in _TREE_AXIS_FIELDS and v is not None}
    out = pf._replace(**upd)
    if tighten_depth:
        out = out._replace(depth=max(_pointer_max_depth(out.left, out.right),
                                     1))
    return out


def prune_forest(pf: PackedForest, alpha: float) -> PackedForest:
    """Cost-complexity post-pruning over the packed ``gain``/``cover``
    buffers (host-side array surgery; no retraining, no kernel changes).

    Bottom-up weakest-link collapse, the post-fit analogue of XGBoost's
    gamma pruning: any internal node whose children are both terminal and
    whose recorded split gain is ``<= alpha`` becomes a leaf, recursively
    (collapsing a node can expose its parent).  Node ids iterate in reverse
    — both producers emit children with larger ids than their parent, so one
    reverse sweep is a full bottom-up pass.  The collapsed leaf value is the
    cover-weighted mean of its children's leaves (the value the training
    objective would have assigned the merged region), computed in float64
    and cast once to f32; a zero-cover child (heap pass-through routing)
    recovers the live child's leaf bit-exactly.  Orphaned child slots become
    inert: zero leaves, self-loops that nothing points at — `compact_forest`
    removes them.  Rows that never reached a pruned subtree score
    bit-identically to the unpruned forest (surviving paths are untouched).

    ``alpha = 0.0`` removes only gainless splits (pass-through heap routing
    and ties); larger alphas trade accuracy for smaller/faster models.
    """
    if pf.gain is None or pf.cover is None:
        raise ValueError(
            "prune_forest needs the packed gain AND cover tensors; this "
            "forest was packed/checkpointed without them (format_version "
            "< 2) — re-checkpoint from a freshly trained model")
    feat = np.asarray(pf.feat).copy()
    thr = np.asarray(pf.thr).copy()
    left = np.asarray(pf.left).copy()
    right = np.asarray(pf.right).copy()
    leaf = np.asarray(pf.leaf, np.float64).copy()
    gain = np.asarray(pf.gain, np.float32).copy()
    cover = np.asarray(pf.cover, np.float64)
    n_trees, n = feat.shape
    for t in range(n_trees):
        for i in range(n - 1, -1, -1):
            l, r = left[t, i], right[t, i]
            if l == i:                                     # already terminal
                continue
            if left[t, l] != l or left[t, r] != r:         # child still splits
                continue
            if gain[t, i] > alpha:
                continue
            cl, cr = cover[t, l], cover[t, r]
            if cl <= 0.0:                  # pass-through: keep live child
                v = leaf[t, r]
            elif cr <= 0.0:
                v = leaf[t, l]
            else:
                v = (cl * leaf[t, l] + cr * leaf[t, r]) / (cl + cr)
            leaf[t, i] = v
            leaf[t, l] = 0.0
            leaf[t, r] = 0.0
            left[t, i] = right[t, i] = i                   # now terminal
            feat[t, i] = 0
            thr[t, i] = 0
            gain[t, i] = 0.0
    return pf._replace(
        feat=jnp.asarray(feat, jnp.int32), thr=jnp.asarray(thr, jnp.int32),
        left=jnp.asarray(left, jnp.int32), right=jnp.asarray(right,
                                                             jnp.int32),
        leaf=jnp.asarray(leaf.astype(np.float32)),
        gain=jnp.asarray(gain, jnp.float32))


def _reachable_nodes(left: np.ndarray, right: np.ndarray) -> np.ndarray:
    """(T, N) bool: node slots reachable from each tree's root (node 0).

    One forward sweep over ascending ids — children always carry larger ids
    than their parent (both producers), the same invariant
    `_pointer_max_depth` and `explain.paths` exploit.
    """
    n_trees, n = left.shape
    reach = np.zeros((n_trees, n), bool)
    if n == 0:
        return reach
    reach[:, 0] = True
    rows = np.arange(n_trees)
    for i in range(n):
        internal = reach[:, i] & (left[:, i] != i)
        r = rows[internal]
        reach[r, left[internal, i]] = True
        reach[r, right[internal, i]] = True
    return reach


def compact_forest(pf):
    """Slot defragmentation: drop unreachable node slots and shrink the node
    axis — pure renumbering, predictions bit-identical (asserted by tests).

    Pruning (and early-exhausted leaf-wise growth) leaves dead slots below
    ``node_count``: orphaned subtrees no pointer reaches.  This pass keeps
    only root-reachable nodes, renumbers them in ascending old-id order
    (preserving the parent < child invariant every consumer relies on),
    remaps the pointers, and pads the new node axis to a multiple of 8 with
    inert self-loop slots.  ``depth`` is recomputed from the surviving
    pointers, so a depth-limited walk over a heavily pruned forest gets
    cheaper, not just smaller.  Works on fp32 and quantized forests alike
    (dtype-preserving gathers).
    """
    left = np.asarray(pf.left)
    right = np.asarray(pf.right)
    n_trees, n = left.shape
    reach = _reachable_nodes(left, right)
    counts = reach.sum(axis=1).astype(np.int32)            # (T,)
    k_max = int(counts.max()) if n_trees else 0
    n_new = max(k_max + (-k_max) % 8, 8)

    def blank_like(x, extra=()):
        return np.zeros((n_trees, n_new) + tuple(extra), np.asarray(x).dtype)

    feat_n = blank_like(pf.feat)
    thr_n = blank_like(pf.thr)
    leaf_n = blank_like(pf.leaf, extra=(pf.leaf.shape[2],))
    cover_n = None if pf.cover is None else blank_like(pf.cover)
    gain_n = None if pf.gain is None else blank_like(pf.gain)
    # Padding slots self-loop so they are inert under the fixed-depth walk.
    iota = np.arange(n_new, dtype=np.int32)
    left_n = np.broadcast_to(iota, (n_trees, n_new)).copy()
    right_n = left_n.copy()

    feat = np.asarray(pf.feat)
    thr = np.asarray(pf.thr)
    leaf = np.asarray(pf.leaf)
    cover = None if pf.cover is None else np.asarray(pf.cover)
    gain = None if pf.gain is None else np.asarray(pf.gain)
    for t in range(n_trees):
        keep = np.flatnonzero(reach[t])                    # ascending old ids
        k = keep.size
        remap = np.zeros(n, np.int64)
        remap[keep] = np.arange(k)
        feat_n[t, :k] = feat[t, keep]
        thr_n[t, :k] = thr[t, keep]
        leaf_n[t, :k] = leaf[t, keep]
        if cover is not None:
            cover_n[t, :k] = cover[t, keep]
        if gain is not None:
            gain_n[t, :k] = gain[t, keep]
        lk, rk = left[t, keep], right[t, keep]
        term = lk == keep
        left_n[t, :k] = np.where(term, np.arange(k), remap[lk])
        right_n[t, :k] = np.where(term, np.arange(k), remap[rk])
    upd = dict(
        feat=jnp.asarray(feat_n), thr=jnp.asarray(thr_n),
        left=jnp.asarray(left_n), right=jnp.asarray(right_n),
        leaf=jnp.asarray(leaf_n),
        cover=None if cover_n is None else jnp.asarray(cover_n),
        gain=None if gain_n is None else jnp.asarray(gain_n),
        node_count=jnp.asarray(counts),
        depth=max(_pointer_max_depth(left_n, right_n), 1))
    return pf._replace(**upd)


# ---------------------------------------------------------------------------
# Inference entry points.
# ---------------------------------------------------------------------------

def forest_apply(F_init: jax.Array, codes: jax.Array, feat: jax.Array,
                 thr: jax.Array, left: jax.Array, right: jax.Array,
                 leaf: jax.Array, out_col: jax.Array, lr,
                 *, depth: int, mode="jnp") -> jax.Array:
    """``F_init + lr * sum_t tree_t(codes)`` under a resolved kernel mode.

    The single traversal primitive shared by serving (`predict_raw`), staged
    eval (`predict_staged`), and the training loop's on-device validation
    update (`boosting._apply_tree`) — all three therefore run the same
    Pallas kernel on TPU and the same gather walk elsewhere.  Accumulation
    is tree-by-tree in both modes, so results are bit-identical across them.
    """
    from repro.kernels import ops as kops
    mode, interp = kops.resolve_dispatch(mode)
    if mode != "jnp":
        return kops.forest_apply(F_init, codes, feat, thr, left, right,
                                 leaf, out_col, lr, depth=depth,
                                 interpret=interp)
    from repro.kernels import ref
    return ref.forest_apply_ref(F_init, codes, feat, thr, left, right, leaf,
                                out_col, jnp.float32(lr), depth=depth)


def forest_apply_quant(F_init: jax.Array, codes: jax.Array, feat: jax.Array,
                       thr: jax.Array, left: jax.Array, right: jax.Array,
                       leaf: jax.Array, leaf_scale: jax.Array,
                       out_col: jax.Array, lr, *, depth: int,
                       mode="jnp") -> jax.Array:
    """Quantized-forest traversal under the same ``use_kernel`` resolution
    as `forest_apply`: uint8/int-code thresholds, int8/bf16 leaf blocks
    dequantized in-flight (``astype(f32) * leaf_scale[t]``), fp32
    accumulation.  Split decisions match the fp32 walk exactly (thresholds
    are bin codes); the result is bit-identical to `forest_apply` on
    `core.quantize.dequantize_forest` of the same model."""
    from repro.kernels import ops as kops
    mode, interp = kops.resolve_dispatch(mode)
    if mode != "jnp":
        return kops.forest_apply_quant(F_init, codes, feat, thr, left, right,
                                       leaf, leaf_scale, out_col, lr,
                                       depth=depth, interpret=interp)
    from repro.kernels import ref
    return ref.forest_apply_quant_ref(F_init, codes, feat, thr, left, right,
                                      leaf, leaf_scale, out_col,
                                      jnp.float32(lr), depth=depth)


def _apply_forest_chunk(pf, F0: jax.Array, part: jax.Array,
                        mode) -> jax.Array:
    """One chunk through the right traversal for the forest's storage:
    quantized forests (recognized by their ``leaf_scale`` field) take the
    dequantizing path, fp32 forests the plain one."""
    scale = getattr(pf, "leaf_scale", None)
    if scale is None:
        return forest_apply(F0, part, pf.feat, pf.thr, pf.left, pf.right,
                            pf.leaf, pf.out_col, pf.lr, depth=pf.depth,
                            mode=mode)
    return forest_apply_quant(F0, part, pf.feat, pf.thr, pf.left, pf.right,
                              pf.leaf, scale, pf.out_col, pf.lr,
                              depth=pf.depth, mode=mode)


def predict_raw(pf, codes: jax.Array, *, mode="jnp",
                row_chunk: int = 0) -> jax.Array:
    """Raw ensemble scores ``F(x) = base + lr * sum_t f_t(x)``, streamed in
    row chunks.  Accepts a fp32 `PackedForest` or a
    `core.quantize.QuantizedForest` (dispatched by storage).

    ``row_chunk > 0`` bounds the per-dispatch working set (rows x outputs
    stay resident on-device; the forest is revisited per chunk): chunk i is
    scored while chunk i+1's codes transfer, and every chunk reuses one
    compiled executable — the last chunk is zero-padded to the chunk size so
    no second trace is ever cut.  ``row_chunk == 0`` scores everything in
    one dispatch.
    """
    n, d = codes.shape[0], pf.n_outputs
    chunk = n if row_chunk <= 0 else min(row_chunk, n)
    outs = []
    for s in range(0, n, chunk):
        part = codes[s:s + chunk]
        if part.shape[0] < chunk:                 # pad tail, keep one trace
            part = jnp.pad(part, ((0, chunk - part.shape[0]), (0, 0)))
        F0 = jnp.broadcast_to(pf.base, (chunk, d)).astype(jnp.float32)
        outs.append(_apply_forest_chunk(pf, F0, part, mode))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return out[:n]


def predict_raw_pipelined(pf, codes, *, mode="jnp",
                          row_chunk: int = 8192) -> jax.Array:
    """Double-buffered `predict_raw`: overlap host->device copies with
    traversal.

    The host slices chunk ``i+1`` and enqueues its ``jax.device_put``
    BEFORE dispatching the traversal of chunk ``i`` — JAX's async dispatch
    then runs the copy and the compute concurrently, so a request stream
    larger than one chunk pays max(copy, compute) per chunk instead of
    copy + compute.  Every chunk reuses one compiled executable (the tail is
    zero-padded) and the per-chunk arithmetic is identical to `predict_raw`,
    so results are bit-equal — asserted by the serving-tier tests.  The
    `forest_apply` F_init buffer is donated, so each chunk's accumulator is
    updated in place rather than reallocated.
    """
    codes_h = np.asarray(codes)
    n, d = codes_h.shape[0], pf.n_outputs
    chunk = min(max(int(row_chunk), 1), n) if n else 1
    starts = list(range(0, n, chunk))

    def stage(s):
        part = codes_h[s:s + chunk]
        if part.shape[0] < chunk:
            part = np.pad(part, ((0, chunk - part.shape[0]), (0, 0)))
        return jax.device_put(jnp.asarray(part))   # async H2D begins now

    buf = stage(starts[0]) if starts else None
    outs = []
    for idx, s in enumerate(starts):
        nxt = stage(starts[idx + 1]) if idx + 1 < len(starts) else None
        F0 = jnp.broadcast_to(pf.base, (chunk, d)).astype(jnp.float32)
        outs.append(_apply_forest_chunk(pf, F0, buf, mode))
        buf = nxt
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("depth", "trees_per_round",
                                             "mode"))
def _staged_scan(codes, feat, thr, left, right, leaf, out_col, base, lr,
                 *, depth: int, trees_per_round: int, mode: str):
    n, d = codes.shape[0], base.shape[0]
    n_rounds = feat.shape[0] // trees_per_round

    def per_round(F, xs):
        f, th, lf, rg, v, col = xs
        F = forest_apply(F, codes, f, th, lf, rg, v, col, lr, depth=depth,
                         mode=mode)
        return F, F

    def group(x):
        return x.reshape((n_rounds, trees_per_round) + x.shape[1:])

    F0 = jnp.broadcast_to(base, (n, d)).astype(jnp.float32)
    _, staged = jax.lax.scan(per_round, F0,
                             (group(feat), group(thr), group(left),
                              group(right), group(leaf), group(out_col)))
    return staged


def predict_staged(pf: PackedForest, codes: jax.Array, *, mode="jnp"
                   ) -> jax.Array:
    """Cumulative raw scores after every boosting round: ``(n_rounds, n, d)``.

    One compiled scan over round groups (1 tree per round for single_tree,
    d for one_vs_all); ``staged[r]`` equals ``predict_raw`` on
    ``slice_rounds(pf, r + 1)`` bit-for-bit.  Materialises the full
    trajectory — meant for validation-sized inputs (model selection,
    learning curves), not the serving path.
    """
    # Leaves are scaled outside the scan's program, as `predict_raw` does
    # (`kernels.ref.scaled_leaves`); the in-scan factor 1.0 is then exact.
    from repro.kernels import ref
    return _staged_scan(codes, pf.feat, pf.thr, pf.left, pf.right,
                        ref.scaled_leaves(pf.lr, pf.leaf), pf.out_col,
                        pf.base, jnp.float32(1.0), depth=pf.depth,
                        trees_per_round=pf.trees_per_round,
                        mode=H.resolve_kernel_mode(mode))


@functools.partial(jax.jit, static_argnames=("depth", "trees_per_round",
                                             "mode", "loss_name"))
def _staged_eval_scan(codes, Y, feat, thr, left, right, leaf, out_col, base,
                      lr, *, depth: int, trees_per_round: int, mode: str,
                      loss_name: str):
    from repro.core import losses as L
    loss = L.get_loss(loss_name)
    n, d = codes.shape[0], base.shape[0]
    n_rounds = feat.shape[0] // trees_per_round

    def per_round(F, xs):
        f, th, lf, rg, v, col = xs
        F = forest_apply(F, codes, f, th, lf, rg, v, col, lr, depth=depth,
                         mode=mode)
        return F, loss.value(F, Y).astype(jnp.float32)

    def group(x):
        return x.reshape((n_rounds, trees_per_round) + x.shape[1:])

    F0 = jnp.broadcast_to(base, (n, d)).astype(jnp.float32)
    _, vloss = jax.lax.scan(per_round, F0,
                            (group(feat), group(thr), group(left),
                             group(right), group(leaf), group(out_col)))
    return vloss


def staged_eval(pf: PackedForest, codes: jax.Array, Y: jax.Array,
                loss_name: str, *, mode="jnp") -> jax.Array:
    """Per-round validation losses ``(n_rounds,)`` without materialising the
    staged score tensor — argmin gives ``best_iteration`` in one dispatch."""
    return _staged_eval_scan(codes, Y, pf.feat, pf.thr, pf.left, pf.right,
                             pf.leaf, pf.out_col, pf.base, pf.lr,
                             depth=pf.depth,
                             trees_per_round=pf.trees_per_round,
                             mode=H.resolve_kernel_mode(mode),
                             loss_name=loss_name)
