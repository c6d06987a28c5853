"""Gradient histogram accumulation over (node, feature, bin) cells.

This is the GBDT hot spot (Sec. 3.4: O(n * m * k) per tree level).  The
level-wise grower (`core.tree.grow_tree`) has two engines:

  * ``"subtract"`` (``"auto"``, production) — the **node-partitioned level
    engine**: the grower carries a stable permutation of rows sorted by
    node (`LevelState`, advanced per level by an O(n) fixed-shape radix
    step), so histogram work touches an ``n_bins``-wide one-hot space per
    row — O(n * m * c) per level — and only the smaller child of each
    parent is built, the sibling derived as ``parent − built`` from the
    loop-carried previous-level histograms (fp32 drift bounded and
    asserted by the parity tests).  `build_level_jnp` is its jnp path and
    `kernels.ops.histogram_splits_level` its fused Pallas twin.
  * ``"direct"`` — `build_histograms_jnp` rebuilds every node of a level
    from ``node_pos`` by segment-sum: the reference the engine parity tests
    compare against, jnp in every kernel mode.

The leaf-wise grower reuses the same idea per node (`NodePartition`).
``resolve_hist_engine`` normalises the engine request.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

KERNEL_MODES = ("jnp", "pallas", "interpret")

HIST_ENGINES = ("direct", "subtract")


def resolve_kernel_mode(use_kernel) -> str:
    """Normalize a kernel request into one of ``KERNEL_MODES``.

    ``True`` means *auto*: the compiled Mosaic kernel on TPU, otherwise the
    jnp reference path (numerically identical, parity-checked by the kernel
    tests) — Pallas interpret mode is a correctness/debugging tool, far too
    slow to be a CPU execution engine.  Set ``REPRO_PALLAS_INTERPRET=1`` (or
    pass ``"interpret"`` explicitly) to force interpret mode off-TPU.
    """
    if use_kernel is False:
        return "jnp"
    if use_kernel is True:
        if jax.default_backend() == "tpu":
            return "pallas"
        if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
            return "interpret"
        return "jnp"
    if use_kernel not in KERNEL_MODES:
        raise ValueError(f"unknown kernel mode {use_kernel!r}; "
                         f"expected bool or one of {KERNEL_MODES}")
    return use_kernel


def resolve_hist_engine(engine) -> str:
    """Normalize a histogram-engine request into one of ``HIST_ENGINES``.

    ``"auto"`` (the default everywhere) resolves to ``"subtract"`` — the
    partitioned builder plus sibling subtraction, the fastest engine on
    every backend.  ``"direct"`` is the full-rebuild jnp reference.
    """
    if engine in (None, "auto"):
        return "subtract"
    if engine not in HIST_ENGINES:
        raise ValueError(f"unknown hist engine {engine!r}; "
                         f"expected 'auto' or one of {HIST_ENGINES}")
    return engine


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins"))
def build_histograms_jnp(codes: jax.Array, node_pos: jax.Array, stats: jax.Array,
                         *, n_nodes: int, n_bins: int) -> jax.Array:
    """Pure-jnp direct histogram builder (the engines' reference).

    Args:
      codes:    (n, m) uint8/int feature bin codes.
      node_pos: (n,) int32 position of each sample within the current tree level.
      stats:    (n, c) float32 per-sample statistics (sketched gradients + count
                channel, or [G | H] for the leaf pass).
    Returns:
      (n_nodes, m, n_bins, c) float32 histograms.
    """
    n, m = codes.shape
    c = stats.shape[1]
    seg_base = node_pos.astype(jnp.int32) * n_bins

    def per_feature(col: jax.Array) -> jax.Array:          # col: (n,)
        seg = seg_base + col.astype(jnp.int32)
        return jax.ops.segment_sum(stats, seg, num_segments=n_nodes * n_bins,
                                   indices_are_sorted=False)

    hist = jax.vmap(per_feature, in_axes=1)(codes)          # (m, nodes*B, c)
    return hist.reshape(m, n_nodes, n_bins, c).transpose(1, 0, 2, 3)


# ---------------------------------------------------------------------------
# Node-partitioned level state: rows kept sorted by node across levels.
# ---------------------------------------------------------------------------

class LevelState(NamedTuple):
    """Loop-carried row partition for one tree level.

    ``order`` is a permutation of ``[0, n)`` such that ``node_perm[i]`` (the
    node of row ``order[i]``) is non-decreasing — rows of each node form one
    contiguous block whose extent is ``counts`` (exclusive-cumsum gives the
    block starts).  The partition is advanced one level at a time by
    `advance_level_state`, a *stable* in-segment 1-bit radix step, so row
    order within a node is the original dataset order — summation order
    (and therefore fp32 histogram bits) is reproducible run to run.
    """
    order: jax.Array      # (n,) int32 row permutation, sorted by node
    node_perm: jax.Array  # (n,) int32 node of order[i] (non-decreasing)
    counts: jax.Array     # (n_nodes,) int32 rows per node


def init_level_state(n: int) -> LevelState:
    """Level-0 partition: every row in the root node, identity order."""
    return LevelState(order=jnp.arange(n, dtype=jnp.int32),
                      node_perm=jnp.zeros((n,), jnp.int32),
                      counts=jnp.full((1,), n, jnp.int32))


@jax.jit
def advance_level_state(state: LevelState, go_right: jax.Array) -> LevelState:
    """Advance the partition one level: parent ``p`` -> children ``2p, 2p+1``.

    ``go_right`` is the per-row routing bit in ORIGINAL row order (as
    produced by the split just found).  The update is an O(n) stable radix
    partition with fixed shapes: within each parent segment, left-routed
    rows keep their relative order and land in child ``2p``, right-routed
    rows in ``2p+1``.
    """
    n = state.order.shape[0]
    n_nodes = state.counts.shape[0]
    bit = go_right.astype(jnp.int32)[state.order]           # permuted order
    parent = state.node_perm
    starts = jnp.cumsum(state.counts) - state.counts        # excl cumsum

    left_counts = jax.ops.segment_sum((1 - bit).astype(jnp.int32), parent,
                                      num_segments=n_nodes,
                                      indices_are_sorted=True)
    counts_new = jnp.stack([left_counts, state.counts - left_counts],
                           axis=1).reshape(-1)              # (2*n_nodes,)
    starts_new = jnp.cumsum(counts_new) - counts_new

    # Stable in-segment ranks from one global exclusive cumsum of the bit.
    pre_left = jnp.cumsum(1 - bit) - (1 - bit)              # lefts before i
    seg_start = starts[parent]
    lefts_in_seg = pre_left - jnp.take(pre_left, seg_start)
    offset_in_seg = jnp.arange(n, dtype=jnp.int32) - seg_start
    rank = jnp.where(bit == 0, lefts_in_seg, offset_in_seg - lefts_in_seg)
    child = 2 * parent + bit
    dest = jnp.take(starts_new, child) + rank               # a permutation

    order_new = jnp.zeros((n,), jnp.int32).at[dest].set(state.order)
    node_new = jnp.zeros((n,), jnp.int32).at[dest].set(child)
    return LevelState(order=order_new, node_perm=node_new, counts=counts_new)


def smaller_children(counts: jax.Array):
    """Per-parent smaller-child selection for sibling subtraction.

    Args:
      counts: (n_nodes,) per-node row counts at the current level.
    Returns:
      ``(side, is_built)`` — ``side[p]`` in {0, 1} is the smaller child of
      parent ``p`` (ties -> left, so the choice is deterministic), and
      ``is_built[c]`` marks the child nodes built directly; the sibling is
      derived as ``parent − built``.
    """
    n_nodes = counts.shape[0]
    side = (counts[0::2] > counts[1::2]).astype(jnp.int32)  # 1: left bigger
    child = jnp.arange(n_nodes, dtype=jnp.int32)
    is_built = (child % 2) == side[child // 2]
    return side, is_built


def interleave_children(side: jax.Array, built4: jax.Array,
                        sib4: jax.Array) -> jax.Array:
    """(P, ...) built/derived sibling pairs -> (2P, ...) child-ordered.

    The one place the built-vs-derived placement rule lives: child ``2p``
    is the built histogram iff ``side[p] == 0``.  Shared by the jnp engine,
    the fused Pallas wrapper (`kernels.ops.histogram_splits_level`), and
    the sharded level loop of `core.tree.grow_tree`, so the three can never
    disagree.
    """
    P = built4.shape[0]
    s = side.reshape((P,) + (1,) * (built4.ndim - 1))
    left = jnp.where(s == 0, built4, sib4)
    right = jnp.where(s == 0, sib4, built4)
    return jnp.stack([left, right], axis=1).reshape((2 * P,) + built4.shape[1:])


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "n_build"))
def build_level_built(codes: jax.Array, stats: jax.Array, state: LevelState,
                      side: jax.Array, *, n_nodes: int, n_bins: int,
                      n_build: int) -> jax.Array:
    """Compacted built-children accumulation: ``(n_nodes/2, m, n_bins, c)``.

    The subtraction engine's direct-build half, factored out so the
    sharded level loop (`core.tree.grow_tree` with ``psum_axes``) can
    reduce it before the subtraction, with a *globally* chosen ``side``:
    ``side[p]`` selects which child of parent ``p`` is accumulated.  On one
    device it comes from `smaller_children(state.counts)`; under sharding it
    must come from the psummed global counts — the locally-built child can
    then hold MORE than ``n // 2`` local rows (a shard may own mostly
    rows of the globally-smaller side), which is why ``n_build`` is a
    parameter: a too-small buffer would silently drop rows (``mode="drop"``
    below), corrupting histograms with no shape error.  Padding slots carry
    zero stats appended after all real rows, so the per-cell summation
    order — and therefore the fp32 bits — is identical for any
    ``n_build`` that bounds the built row count.
    """
    n, m = codes.shape
    B = n_bins
    P = n_nodes // 2
    # Compact the built-children rows into the fixed buffer: rows of node c
    # are contiguous in partition order, so a mask + exclusive cumsum gives
    # each built row its destination slot.
    mask = (state.node_perm % 2) == side[state.node_perm // 2]
    dest = jnp.cumsum(mask.astype(jnp.int32)) - mask.astype(jnp.int32)
    gather = jnp.full((n_build,), n, jnp.int32).at[
        jnp.where(mask, dest, n_build)].set(jnp.arange(n, dtype=jnp.int32),
                                            mode="drop")
    valid = gather < n
    ri = state.order[jnp.minimum(gather, n - 1)]
    p_g = jnp.where(valid, state.node_perm[jnp.minimum(gather, n - 1)] // 2, 0)
    stats_g = stats[ri] * valid[:, None].astype(stats.dtype)

    def per_feature(col):
        return jax.ops.segment_sum(stats_g, p_g * B + col[ri],
                                   num_segments=P * B)

    built = jax.vmap(per_feature, in_axes=1)(codes.astype(jnp.int32))
    return built.reshape(m, P, B, -1).transpose(1, 0, 2, 3)   # (P, m, B, c)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "subtract"))
def build_level_jnp(codes: jax.Array, stats: jax.Array, state: LevelState,
                    prev_hist: Optional[jax.Array], *, n_nodes: int,
                    n_bins: int, subtract: bool) -> jax.Array:
    """jnp reference path of the partitioned level engine.

    Builds the ``(n_nodes, m, n_bins, c)`` histograms of one level from the
    partition state.  With ``subtract=True`` (level > 0) only the smaller
    child of each parent is accumulated — over a fixed-size ``n // 2`` row
    buffer gathered from the contiguous child segments (`build_level_built`)
    — and the sibling is derived from ``prev_hist`` (the previous level's
    histograms).
    """
    n, m = codes.shape
    B = n_bins
    if not subtract:
        # Partitioned build of every node: segment-sum over rows in
        # partition order (node-major segment ids).
        ri = state.order
        seg_base = state.node_perm * B

        def per_feature(col):
            return jax.ops.segment_sum(stats[ri], seg_base + col[ri],
                                       num_segments=n_nodes * B)

        hist = jax.vmap(per_feature, in_axes=1)(codes.astype(jnp.int32))
        return hist.reshape(m, n_nodes, B, -1).transpose(1, 0, 2, 3)

    side, _ = smaller_children(state.counts)
    built4 = build_level_built(codes, stats, state, side, n_nodes=n_nodes,
                               n_bins=n_bins, n_build=max(n // 2, 1))
    sib4 = prev_hist - built4
    return interleave_children(side, built4, sib4)


# ---------------------------------------------------------------------------
# Per-node partition for the leaf-wise (best-first) grower: the same stable
# radix idea as `advance_level_state`, but splitting ONE node's contiguous
# segment at a time over a sparse node id space.
# ---------------------------------------------------------------------------

class NodePartition(NamedTuple):
    """Row partition over sparse node ids (leaf-wise grower loop state).

    ``order`` is a permutation of ``[0, n)`` whose positions
    ``[starts[j], starts[j] + counts[j])`` hold the rows of node ``j`` in
    original dataset order (stability — summation order and therefore fp32
    histogram bits are reproducible, and match the level engine's compacted
    builds for the same row sets).  Unlike `LevelState`, segments are NOT
    sorted by node id: `split_partition_at` splits one segment in place, so
    children inherit their parent's position in ``order``.
    """
    order: jax.Array      # (n,) int32 row permutation
    node_perm: jax.Array  # (n,) int32 node id at each position
    starts: jax.Array     # (n_slots,) int32 segment starts
    counts: jax.Array     # (n_slots,) int32 rows per node


def init_node_partition(n: int, n_slots: int) -> NodePartition:
    """Every row in root node 0; unused slots empty."""
    return NodePartition(
        order=jnp.arange(n, dtype=jnp.int32),
        node_perm=jnp.zeros((n,), jnp.int32),
        starts=jnp.zeros((n_slots,), jnp.int32),
        counts=jnp.zeros((n_slots,), jnp.int32).at[0].set(n))


@jax.jit
def split_partition_at(part: NodePartition, p: jax.Array, c1: jax.Array,
                       c2: jax.Array, go_right: jax.Array,
                       do: jax.Array) -> NodePartition:
    """Stably split node ``p``'s segment into children ``c1`` (left rows
    first) and ``c2`` — an O(n) fixed-shape scatter touching only the
    segment.  ``go_right`` is the per-row routing bit in ORIGINAL row order;
    ``do=False`` makes the whole update an exact no-op (the masked guard the
    fixed-bound expansion loop relies on after frontier exhaustion).
    """
    n = part.order.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    bit = go_right.astype(jnp.int32)[part.order]
    in_seg = (part.node_perm == p) & do
    sel_left = in_seg & (bit == 0)
    pre_left = jnp.cumsum(sel_left.astype(jnp.int32)) - sel_left
    s0 = part.starts[p]
    lefts_before = pre_left - pre_left[s0]
    offset_in_seg = pos - s0
    rank = jnp.where(bit == 0, lefts_before, offset_in_seg - lefts_before)
    n_left = jnp.sum(sel_left.astype(jnp.int32))
    dest = jnp.where(in_seg,
                     s0 + jnp.where(bit == 0, rank, n_left + rank), pos)
    order = jnp.zeros((n,), jnp.int32).at[dest].set(part.order)
    child = jnp.where(bit == 0, c1, c2)
    node_perm = jnp.zeros((n,), jnp.int32).at[dest].set(
        jnp.where(in_seg, child, part.node_perm))
    n_p = part.counts[p]
    upd = lambda a, i, v: a.at[i].set(jnp.where(do, v, a[i]))
    counts = upd(upd(upd(part.counts, c1, n_left), c2, n_p - n_left), p, 0)
    starts = upd(upd(part.starts, c1, s0), c2, s0 + n_left)
    return NodePartition(order=order, node_perm=node_perm, starts=starts,
                         counts=counts)


def gather_node_rows(part: NodePartition, node: jax.Array, n_buf: int):
    """Fixed-size gather of one node's contiguous rows.

    Returns ``(rows, valid)``: ``rows`` indexes the original dataset
    (clamped on padding slots), ``valid`` masks real rows.  ``n_buf`` must
    statically bound the node's row count (``n // 2`` for any
    smaller-of-two-children, ``n`` for the root).
    """
    n = part.order.shape[0]
    idx = part.starts[node] + jnp.arange(n_buf, dtype=jnp.int32)
    valid = jnp.arange(n_buf, dtype=jnp.int32) < part.counts[node]
    rows = part.order[jnp.clip(idx, 0, n - 1)]
    return rows, valid


@functools.partial(jax.jit, static_argnames=("n_bins",))
def node_hist_jnp(codes_g: jax.Array, stats_g: jax.Array, *, n_bins: int
                  ) -> jax.Array:
    """Single-node histogram from gathered rows: ``(m, n_bins, c)``.

    ``codes_g`` is ``(S, m)`` and ``stats_g`` ``(S, c)`` with padding rows
    already zeroed — the jnp twin of the kernel path's
    `kernels.ops.node_histogram`.  Summation runs in gathered (partition)
    order, matching the level engine's compacted smaller-child builds
    bit-for-bit for identical row sets.
    """

    def per_feature(col):
        return jax.ops.segment_sum(stats_g, col, num_segments=n_bins)

    return jax.vmap(per_feature, in_axes=1)(codes_g.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("n_leaves",))
def leaf_sums(leaf_pos: jax.Array, G: jax.Array, H: jax.Array,
              *, n_leaves: int):
    """Per-leaf full-gradient sums for the leaf-value pass (eq. (3)).

    Unlike the split search this uses the *full* (n, d) gradients/Hessians.
    Returns (G_sum, H_sum), each (n_leaves, d).
    """
    gs = jax.ops.segment_sum(G, leaf_pos.astype(jnp.int32), num_segments=n_leaves)
    hs = jax.ops.segment_sum(H, leaf_pos.astype(jnp.int32), num_segments=n_leaves)
    return gs, hs
