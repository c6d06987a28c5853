"""Jit'd public wrappers around the Pallas kernels (padding, layout, dispatch).

Each op pads inputs to kernel tile multiples, calls the kernel and unpads.
The GBDT ops take ``interpret`` with no default: ``False`` runs the compiled
Mosaic kernel (TPU), ``True`` the Pallas interpreter (CPU parity tests), so
a caller cannot land in the interpreter on the chip by omission.  ``*_ref``
semantics are defined in `repro.kernels.ref`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.hist_kernel import (hist_parts, hist_tiles_pallas,
                                      stat_parts)
from repro.kernels.predict_kernel import (forest_traverse_pallas,
                                          forest_traverse_quant_pallas)
from repro.kernels.ref import SHAP_BIG_BIN as _SHAP_BIG
from repro.kernels.shap_kernel import shap_pallas
from repro.kernels.split_kernel import split_scan_pallas
from repro.runtime import tracing as TR


def resolve_dispatch(use_kernel, interpret: bool | None = None):
    """Shared kernel-dispatch resolution: ``(mode, interpret_flag)``.

    The kernel-dispatching entry points outside the growers — the forest
    traversal (`core.forest.forest_apply`) and TreeSHAP (`explain.shap`) —
    resolve their ``use_kernel`` request through this one helper so they
    can never drift.
    ``interpret`` is the legacy explicit override: with any kernel request
    (even one that auto-resolved to the jnp fallback), ``interpret=True``
    forces the Pallas interpreter and ``interpret=False`` the compiled
    Mosaic kernel.
    """
    from repro.core.histogram import resolve_kernel_mode
    mode = resolve_kernel_mode(use_kernel)
    if interpret is not None and use_kernel not in (False, "jnp"):
        mode = "interpret" if interpret else "pallas"
    return mode, mode == "interpret"


def _resolve_lane_pad(lane_pad: int | None, interpret: bool) -> int:
    """Channel-axis padding: full 128-lane MXU/VPU alignment for the compiled
    Mosaic path, 8 in interpret mode to keep CPU parity tests cheap."""
    if lane_pad is not None:
        return lane_pad
    return 8 if interpret else 128


def _pad_to(x: jax.Array, mult: int, axis: int, value=0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "m_tile",
                                             "lane_pad", "interpret"))
def split_scan(hist: jax.Array, lam: jax.Array, min_data: jax.Array,
               feature_mask: jax.Array | None = None, *, n_nodes: int,
               n_bins: int, m_tile: int = 8, lane_pad: int | None = None,
               interpret: bool):
    """(m, n_nodes * n_bins, c) histograms -> per-node (best_gain, best_idx).

    ``best_idx`` encodes ``feature * n_bins + bin``; gain is -inf for nodes
    with no legal split.  Pads the feature axis to ``m_tile`` (padded features
    are masked out) and the channel axis to ``lane_pad`` (zero channels add
    nothing to the squared norms).
    """
    m, _, c = hist.shape
    lane_pad = _resolve_lane_pad(lane_pad, interpret)
    mask = (jnp.ones((m,), jnp.float32) if feature_mask is None
            else feature_mask.astype(jnp.float32))
    hist_p = _pad_to(_pad_to(hist.astype(jnp.float32), lane_pad, axis=2),
                     m_tile, axis=0)
    mask_p = _pad_to(mask, m_tile, axis=0)[:, None]
    params = jnp.stack([jnp.float32(lam), jnp.float32(min_data)])[None, :]
    gain, idx = split_scan_pallas(hist_p, params, mask_p, n_nodes=n_nodes,
                                  n_bins=n_bins, n_channels=c, m_tile=m_tile,
                                  lane_pad=lane_pad, interpret=interpret)
    return gain[:, 0], idx[:, 0]


def _tile_plan(counts: jax.Array, build_counts: jax.Array, *, n: int,
               n_tiles: int, row_tile: int):
    """Node-contiguous tile layout for the partitioned histogram kernel.

    Every node gets ``max(ceil(build_counts / row_tile), 1)`` tiles (the
    kernel writes a node's output block only from that node's tiles, so an
    unbuilt/empty node gets one all-padding tile, which writes zeros), so
    each tile belongs to exactly one node; the trailing tiles beyond the
    last node's run map to the last node and carry zero stats.  Returns ``(tile_node,
    src_perm, valid)``: the node of each tile, each row slot's index into the
    *partition-ordered* row sequence, and the real-row mask (padding slots
    carry zero stats).  All shapes are static; ``n_tiles`` must be
    >= ``n_build // row_tile + 1 + n_nodes`` (the callers' static bound).
    """
    n_nodes = counts.shape[0]
    starts = jnp.cumsum(counts) - counts
    t_c = jnp.maximum((build_counts + row_tile - 1) // row_tile, 1)
    tile_starts = jnp.cumsum(t_c) - t_c
    tile_node = jnp.searchsorted(jnp.cumsum(t_c),
                                 jnp.arange(n_tiles, dtype=jnp.int32),
                                 side="right")
    tile_node = jnp.minimum(tile_node, n_nodes - 1).astype(jnp.int32)
    slot = jnp.arange(n_tiles * row_tile, dtype=jnp.int32)
    t_of = slot // row_tile
    node_of = tile_node[t_of]
    pos_in_node = (t_of - tile_starts[node_of]) * row_tile + slot % row_tile
    valid = pos_in_node < build_counts[node_of]
    src_perm = jnp.minimum(starts[node_of] + pos_in_node, n - 1)
    return tile_node, src_perm, valid


@functools.partial(jax.jit, static_argnames=("n_nodes", "n_bins", "subtract",
                                             "row_tile", "m_tile", "lane_pad",
                                             "hist_dtype", "interpret"))
def histogram_splits_level(codes: jax.Array, stats: jax.Array,
                           order: jax.Array, counts: jax.Array,
                           prev_hist: jax.Array | None,
                           lam: jax.Array, min_data: jax.Array,
                           feature_mask: jax.Array | None = None, *,
                           n_nodes: int, n_bins: int, subtract: bool = False,
                           row_tile: int = 256, m_tile: int = 8,
                           lane_pad: int | None = None,
                           hist_dtype: str = "float32",
                           interpret: bool):
    """Fused partitioned hot path: tiles kernel -> sibling combine -> split scan.

    Rows are gathered into node-contiguous tiles (`_tile_plan` over the
    grower's loop-carried `core.histogram.LevelState` permutation), and the
    tiles kernel contracts an ``n_bins``-wide one-hot space per tile —
    O(n * m * c) per level, at any depth — summing each node's tiles in
    that node's output block, so it returns the node histograms already in
    the split kernel's feature-major layout.  With ``subtract=True`` only
    the smaller child of each parent is built (by row count; ties -> left)
    and the sibling is derived as ``parent − built`` from ``prev_hist``, the
    previous level's returned histograms — halving the tile work again and
    keeping the count channel of the directly-built side exact.

    Args:
      codes: (n, m); stats: (n, c); order: (n,) partition permutation;
      counts: (n_nodes,) per-node row counts; prev_hist: the previous
      level's ``(m, (n_nodes // 2) * n_bins, C_pad)`` histograms (required
      iff ``subtract``).
    Returns:
      ``(best_gain, best_idx, hist_native)`` — per-node split results as in
      `split_scan` plus this level's lane-padded native histograms to carry.
    """
    from repro.core.histogram import interleave_children, smaller_children
    n, m = codes.shape
    c = stats.shape[1]
    lane_pad = _resolve_lane_pad(lane_pad, interpret)
    b_pad = n_bins + (-n_bins) % 8               # sublane-aligned bin axis
    with jax.named_scope(TR.GATHER):
        if subtract:
            P = n_nodes // 2
            side, is_built = smaller_children(counts)
            build_counts = jnp.where(is_built, counts, 0)
            n_eff = n // 2                       # smaller halves sum <= n/2
        else:
            build_counts = counts
            n_eff = n
        n_tiles = n_eff // row_tile + 1 + n_nodes
        tile_node, src_perm, valid = _tile_plan(counts, build_counts, n=n,
                                                n_tiles=n_tiles,
                                                row_tile=row_tile)
        ri = order[src_perm]
        codes_t = codes[ri].astype(jnp.int32).T              # (m, S)
        stats_g = stats.astype(jnp.float32)[ri] * valid[:, None]
        n_parts = hist_parts(hist_dtype)
        parts = stat_parts(stats_g, n_parts=n_parts, lane_pad=lane_pad)
    nodes = hist_tiles_pallas(tile_node, codes_t, parts, n_nodes=n_nodes,
                              n_bins=b_pad, n_channels=c, n_parts=n_parts,
                              lane_pad=lane_pad, row_tile=row_tile,
                              interpret=interpret)
    with jax.named_scope(TR.EPILOGUE):
        nodes = nodes[:, :, :n_bins, :]                    # (m, nodes, B, C)
    if subtract:
        with jax.named_scope(TR.SUBTRACT):
            prev = prev_hist.reshape(m, P, n_bins, -1)
            pairs = nodes.reshape(m, P, 2, n_bins, -1)
            built = jnp.where(side.reshape(1, P, 1, 1) == 0,
                              pairs[:, :, 0], pairs[:, :, 1])
            nodes = jax.vmap(interleave_children, in_axes=(None, 0, 0))(
                side, built, prev - built)
    with jax.named_scope(TR.EPILOGUE):
        hist_native = nodes.reshape(m, n_nodes * n_bins, -1)
        mask = (jnp.ones((m,), jnp.float32) if feature_mask is None
                else feature_mask.astype(jnp.float32))
        hist_p = _pad_to(hist_native, m_tile, axis=0)
        mask_p = _pad_to(mask, m_tile, axis=0)[:, None]
        params = jnp.stack([jnp.float32(lam), jnp.float32(min_data)])[None, :]
    gain, idx = split_scan_pallas(hist_p, params, mask_p, n_nodes=n_nodes,
                                  n_bins=n_bins, n_channels=c, m_tile=m_tile,
                                  lane_pad=lane_pad, interpret=interpret)
    with jax.named_scope(TR.EPILOGUE):
        return gain[:, 0], idx[:, 0], hist_native


@functools.partial(jax.jit, static_argnames=("n_bins", "row_tile",
                                             "lane_pad", "hist_dtype",
                                             "interpret"))
def node_histogram(codes_g: jax.Array, stats_g: jax.Array, *, n_bins: int,
                   row_tile: int = 256, lane_pad: int | None = None,
                   hist_dtype: str = "float32",
                   interpret: bool) -> jax.Array:
    """Single-node histogram over gathered rows: ``(m, n_bins, c)``.

    The leaf-wise grower's kernel-path builder: ``codes_g`` (S, m) /
    ``stats_g`` (S, c) hold ONE node's rows in partition order (padding rows
    carry zero stats).  Rows are tiled through `hist_tiles_pallas` with
    every tile mapped to node 0, so the kernel sums them in tile order —
    the same accumulation it performs per node in the level engine.
    Semantics contract: `core.histogram.node_hist_jnp`.
    """
    c = stats_g.shape[1]
    lane_pad = _resolve_lane_pad(lane_pad, interpret)
    b_pad = n_bins + (-n_bins) % 8               # sublane-aligned bin axis
    codes_t = _pad_to(codes_g.T.astype(jnp.int32), row_tile, axis=1)
    n_parts = hist_parts(hist_dtype)
    parts = _pad_to(stat_parts(stats_g, n_parts=n_parts, lane_pad=lane_pad),
                    row_tile, axis=0)
    n_tiles = codes_t.shape[1] // row_tile
    hist = hist_tiles_pallas(jnp.zeros((n_tiles,), jnp.int32), codes_t,
                             parts, n_nodes=1, n_bins=b_pad, n_channels=c,
                             n_parts=n_parts, lane_pad=lane_pad,
                             row_tile=row_tile, interpret=interpret)
    return hist[:, 0, :n_bins, :c]


@functools.partial(jax.jit,
                   static_argnames=("depth", "row_tile", "lane_pad",
                                    "interpret"),
                   donate_argnums=(0,))
def forest_apply(F_init: jax.Array, codes: jax.Array, feat: jax.Array,
                 thr: jax.Array, left: jax.Array, right: jax.Array,
                 leaf: jax.Array, out_col: jax.Array,
                 lr, *, depth: int, row_tile: int = 256,
                 lane_pad: int | None = None,
                 interpret: bool) -> jax.Array:
    """Packed-forest traversal: ``F_init + lr * sum_t tree_t(codes)``.

    Pads rows to ``row_tile`` and the feature / node / leaf-width / output
    axes to ``lane_pad`` lanes, runs the pointer-chasing traversal kernel
    over the ``(row_tiles, trees)`` grid, and unpads.  Padded rows route
    somewhere harmless and are sliced off; padded node slots are unreachable
    (no real pointer targets them); padded leaf columns are zero and the
    in-kernel placement matrix never scatters them.  Semantics contract:
    `ref.forest_apply_ref`.
    """
    n, m = codes.shape
    d = F_init.shape[1]
    w = leaf.shape[2]
    lane_pad = _resolve_lane_pad(lane_pad, interpret)
    codes_p = _pad_to(_pad_to(codes.astype(jnp.int32), row_tile, axis=0),
                      lane_pad, axis=1)
    F_p = _pad_to(_pad_to(F_init.astype(jnp.float32), row_tile, axis=0),
                  lane_pad, axis=1)
    feat_p = _pad_to(feat.astype(jnp.int32), lane_pad, axis=1)
    thr_p = _pad_to(thr.astype(jnp.int32), lane_pad, axis=1)
    left_p = _pad_to(left.astype(jnp.int32), lane_pad, axis=1)
    right_p = _pad_to(right.astype(jnp.int32), lane_pad, axis=1)
    leaf_p = _pad_to(_pad_to(leaf.astype(jnp.float32), lane_pad, axis=1),
                     lane_pad, axis=2)
    lr_row = jnp.full((1, leaf_p.shape[2]), lr, jnp.float32)
    out = forest_traverse_pallas(lr_row, out_col.astype(jnp.int32), F_p,
                                 codes_p, feat_p, thr_p, left_p, right_p,
                                 leaf_p, depth=depth, leaf_width=w,
                                 row_tile=row_tile, interpret=interpret)
    return out[:n, :d]


@functools.partial(jax.jit,
                   static_argnames=("depth", "row_tile", "lane_pad",
                                    "interpret"),
                   donate_argnums=(0,))
def forest_apply_quant(F_init: jax.Array, codes: jax.Array, feat: jax.Array,
                       thr: jax.Array, left: jax.Array, right: jax.Array,
                       leaf: jax.Array, leaf_scale: jax.Array,
                       out_col: jax.Array, lr, *, depth: int,
                       row_tile: int = 256, lane_pad: int | None = None,
                       interpret: bool) -> jax.Array:
    """Quantized packed-forest traversal (storage-compressed serving path).

    Same padding policy as `forest_apply`; the leaf tensor is padded in its
    OWN dtype (int8 / bfloat16) so the kernel's VMEM working set keeps the
    compression win, thresholds are widened to int32 on the way in (uint8
    bin codes — the walk is split-exact), and dequantization happens
    in-kernel against the per-tree scale row.  Semantics contract:
    `ref.forest_apply_quant_ref`.
    """
    n, m = codes.shape
    d = F_init.shape[1]
    w = leaf.shape[2]
    lane_pad = _resolve_lane_pad(lane_pad, interpret)
    codes_p = _pad_to(_pad_to(codes.astype(jnp.int32), row_tile, axis=0),
                      lane_pad, axis=1)
    F_p = _pad_to(_pad_to(F_init.astype(jnp.float32), row_tile, axis=0),
                  lane_pad, axis=1)
    feat_p = _pad_to(feat.astype(jnp.int32), lane_pad, axis=1)
    thr_p = _pad_to(thr.astype(jnp.int32), lane_pad, axis=1)
    left_p = _pad_to(left.astype(jnp.int32), lane_pad, axis=1)
    right_p = _pad_to(right.astype(jnp.int32), lane_pad, axis=1)
    leaf_p = _pad_to(_pad_to(leaf, lane_pad, axis=1), lane_pad, axis=2)
    w_pad = leaf_p.shape[2]
    lr_row = jnp.full((1, w_pad), lr, jnp.float32)
    scale = jnp.broadcast_to(leaf_scale.astype(jnp.float32).reshape(-1, 1, 1),
                             (leaf.shape[0], 1, w_pad))
    out = forest_traverse_quant_pallas(lr_row, out_col.astype(jnp.int32),
                                       scale, F_p, codes_p, feat_p, thr_p,
                                       left_p, right_p, leaf_p,
                                       depth=depth, leaf_width=w,
                                       row_tile=row_tile,
                                       interpret=interpret)
    return out[:n, :d]


@functools.partial(jax.jit,
                   static_argnames=("n_outputs", "depth", "row_tile",
                                    "lane_pad", "interpret"))
def tree_shap(codes: jax.Array, slot_feat: jax.Array, slot_lo: jax.Array,
              slot_hi: jax.Array, slot_z: jax.Array, leaf: jax.Array,
              out_col: jax.Array, lr, *, n_outputs: int, depth: int,
              row_tile: int | None = None, lane_pad: int | None = None,
              interpret: bool) -> jax.Array:
    """Path-dependent TreeSHAP: ``lr * sum_t shap_t(codes)`` as (n, m, d).

    Takes the `explain.paths.build_path_pack` slot tensors in their native
    ``(T, L, D)`` layout, pads rows to ``row_tile`` and the feature / leaf /
    output axes to ``lane_pad`` lanes, re-lays the slot tensors slot-major
    ``(T, D_pad, L_pad)`` (slot count on sublanes, leaves on lanes), runs the
    path-walk kernel over the ``(row_tiles, trees)`` grid, and unpads.
    Padded leaves/slots are inert null players (``o = z = 1``, zero leaf
    values) — exactly invariant, so the result is bit-identical to the
    unpadded oracle.  Semantics contract: `ref.tree_shap_ref`.
    ``row_tile=None`` sizes the row tile so the ``(row_tile, M_pad, d_pad)``
    output block stays near 2 MiB of VMEM (64 rows at small widths, 8 at
    m=100, d=512).
    """
    n, m = codes.shape
    d = n_outputs
    w = leaf.shape[2]
    lane_pad = _resolve_lane_pad(lane_pad, interpret)
    d_pad = d + (-d) % lane_pad
    if row_tile is None:
        row_bytes = (m + (-m) % lane_pad) * d_pad * 4
        row_tile = 64
        while row_tile > 8 and row_tile * row_bytes > 2 ** 21:
            row_tile //= 2
    codes_p = _pad_to(_pad_to(codes.astype(jnp.int32), row_tile, axis=0),
                      lane_pad, axis=1)
    # Slot tensors: pad the leaf axis with inert slots, then slot-major
    # transpose and pad the (tiny) slot axis to the sublane multiple — those
    # rows are never read (the kernel slices [0:depth]).
    slot_pad = 8

    def lay_out(x, leaf_fill, dtype):
        x = _pad_to(x.astype(dtype), lane_pad, axis=1, value=leaf_fill)
        return _pad_to(x.transpose(0, 2, 1), slot_pad, axis=1,
                       value=leaf_fill)

    sf_p = lay_out(slot_feat, -1, jnp.int32)
    lo_p = lay_out(slot_lo, -1, jnp.int32)
    hi_p = lay_out(slot_hi, _SHAP_BIG, jnp.int32)
    z_p = lay_out(slot_z, 1.0, jnp.float32)
    leaf_p = _pad_to(_pad_to(leaf.astype(jnp.float32), lane_pad, axis=1),
                     lane_pad, axis=2)
    lr_row = jnp.full((1, leaf_p.shape[2]), lr, jnp.float32)
    out = shap_pallas(lr_row, out_col.astype(jnp.int32), codes_p,
                      sf_p, lo_p, hi_p, z_p, leaf_p, depth=depth,
                      leaf_width=w, d_pad=d_pad, row_tile=row_tile,
                      interpret=interpret)
    return out[:n, :m, :d]


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True) -> jax.Array:
    """GQA flash attention; pads sq/sk to tile multiples and unpads."""
    b, hq, sq, dh = q.shape
    sk = k.shape[2]
    block_q = min(block_q, max(8, 1 << (sq - 1).bit_length()))
    block_k = min(block_k, max(8, 1 << (sk - 1).bit_length()))
    qp = _pad_to(q, block_q, axis=2)
    kp = _pad_to(k, block_k, axis=2)
    vp = _pad_to(v, block_k, axis=2)
    out = flash_attention_pallas(qp, kp, vp, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret)
    return out[:, :, :sq]


@functools.partial(jax.jit, static_argnames=("window", "block_s", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lengths: jax.Array, *, window: int | None = None,
                     block_s: int = 512, interpret: bool = True) -> jax.Array:
    """Single-token GQA decode attention; pads the cache axis."""
    s = k.shape[2]
    block_s = min(block_s, max(8, 1 << (s - 1).bit_length()))
    kp = _pad_to(k, block_s, axis=2)
    vp = _pad_to(v, block_s, axis=2)
    return decode_attention_pallas(q, kp, vp, lengths, window=window,
                                   block_s=block_s, interpret=interpret)


# Re-export the oracles for convenience.
histogram_tiles_ref = ref.histogram_tiles_ref
split_scan_ref = ref.split_scan_ref
forest_apply_ref = ref.forest_apply_ref
forest_apply_quant_ref = ref.forest_apply_quant_ref
tree_shap_ref = ref.tree_shap_ref
tree_shap_interventional_ref = ref.tree_shap_interventional_ref
mha_ref = ref.mha_ref
decode_attention_ref = ref.decode_attention_ref
