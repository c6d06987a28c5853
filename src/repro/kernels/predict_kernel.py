"""Pallas TPU kernel: packed-forest traversal (the inference hot spot).

Walks *every tree for a tile of rows* depth-by-depth on-device, the TPU
analogue of the batched GPU tree traversals in XGBoost-GPU (Mitchell et al.,
2018) and Zhang et al. (2017): instead of per-row pointer chasing in scalar
code, each level is a handful of one-hot contractions on the MXU.

Trees arrive in the sparse-topology `core.forest.PackedForest` layout: a
unified node id space with explicit ``left``/``right`` child pointers
(terminal nodes self-loop), so one traversal serves level-wise heaps and
leaf-wise best-first trees alike.  For one row tile and one tree, every
level maintains the node id ``pos`` of each row and advances it with

    sel    = onehot(pos)  @ onehot(feat)^T        (TN, N) @ (M, N)^T
    code   = sum_f sel * codes                    (TN, 1)
    thr, l, r = onehot(pos) @ [thr|left|right]^T  (TN, 8) slot gathers
    pos   <- code > thr ? r : l

After ``depth`` levels ``pos`` is the terminal node (self-loops make extra
iterations exact no-ops); the node-indexed leaf block is gathered with one
more one-hot matmul and scattered into the output columns
``[out_col, out_col + leaf_width)`` through a placement matrix, so the same
kernel serves full-width ``single_tree`` leaves (width d, out_col 0) and
``one_vs_all`` scalar leaves (width 1, out_col j).  Every contraction is an
exact 0/1 selection and pointer values are small exact float32 integers —
the kernel is bit-compatible with the gather-based reference
(`ref.forest_apply_ref`), which the parity tests assert.

Grid = ``(row_tiles, trees)``; the output block for a row tile is revisited
across the sequential tree axis (canonical Pallas accumulation: init from the
``F_init`` scores at ``t == 0``, then ``out += (lr * leaf)[walk]`` per tree
— the same add order as the scan-based reference, so accumulation is also
bit-identical).  VMEM working set per step: codes tile (TN x M x 4B) + node
tensors (4 x N x 4B) + leaf block (N x W x 4B) + out/init tiles
(2 x TN x D x 4B) + the (TN, max(M, N)) one-hot planes — with TN=256,
M=128, N=128 (depth-6 forest), D=512 that is ~3 MB double-buffered, inside
16 MB VMEM.  Versus the heap-walk kernel this pays a ~2x wider one-hot
plane per level (N vs the level width) in exchange for topology freedom.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_HI = jax.lax.Precision.HIGHEST    # fp32 operands: no single bf16 MXU pass


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, dimension_numbers=(contract, ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _walk_and_place(col, init_ref, codes_ref, feat_ref, thr_ref, left_ref,
                    right_ref, leaf, out_ref, *, depth: int,
                    leaf_width: int):
    """One (row tile, tree) grid step.

    ``leaf`` is the tree's fp32 (N, W) block, already multiplied by the
    learning rate, so the accumulating add is the only rounding after the
    exact gathers (as in `ref.scaled_leaves`).
    """
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = init_ref[...]

    codes = codes_ref[...].astype(jnp.float32)             # (TN, M)
    tn, m_pad = codes.shape
    n_pad = feat_ref.shape[1]                              # node id space
    # Node tensors arrive as (1, N) rows; every one-hot is built with the
    # node axis on lanes and contracted as ``A @ B^T``.
    feat_oh_t = (jax.lax.broadcasted_iota(jnp.int32, (m_pad, n_pad), 0)
                 == feat_ref[...]).astype(jnp.float32)     # (M, N)
    k = jax.lax.broadcasted_iota(jnp.int32, (8, n_pad), 0)
    ptrs_t = jnp.where(k == 0, thr_ref[...], jnp.where(
        k == 1, left_ref[...], jnp.where(k == 2, right_ref[...], 0))
    ).astype(jnp.float32)                                  # (8, N) thr/l/r
    node_ids = jax.lax.broadcasted_iota(jnp.int32, (tn, n_pad), 1)
    pos = jnp.zeros((tn, 1), jnp.int32)                    # node id per row

    for _ in range(depth):
        pos_oh = (pos == node_ids).astype(jnp.float32)     # (TN, N)
        sel = _dot(pos_oh, feat_oh_t, ((1,), (1,)))        # (TN, M) split
        code = jnp.sum(sel * codes, axis=1, keepdims=True)  # (TN, 1) exact
        tlr = _dot(pos_oh, ptrs_t, ((1,), (1,)))           # (TN, 8) thr/l/r
        go_right = code > tlr[:, 0:1]
        pos = jnp.where(go_right, tlr[:, 2:3], tlr[:, 1:2]).astype(jnp.int32)

    l_pad = leaf.shape[0]
    leaf_oh = (pos == jax.lax.broadcasted_iota(
        jnp.int32, (tn, l_pad), 1)).astype(jnp.float32)
    pred = _dot(leaf_oh, leaf, ((1,), (0,)))               # (TN, W) leaf block

    # Placement matrix: row i of the leaf block lands in output column
    # out_col + i; rows beyond the real leaf_width are zero padding.
    w_pad, d_pad = pred.shape[1], out_ref.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (w_pad, d_pad), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (w_pad, d_pad), 1)
    place = ((rows < leaf_width) & (rows + col == cols)).astype(jnp.float32)
    out_ref[...] += _dot(pred, place, ((1,), (0,)))


# The learning rate and the dequant scale arrive as (1, W) rows, not SMEM
# scalars: under the interpreter XLA would hoist a scalar factor out of the
# gather matmul and fuse it into the accumulating add as an FMA.

def _forest_kernel(col_ref, lr_ref, init_ref, codes_ref, feat_ref, thr_ref,
                   left_ref, right_ref, leaf_ref, out_ref, *, depth: int,
                   leaf_width: int):
    _walk_and_place(col_ref[pl.program_id(1)], init_ref, codes_ref,
                    feat_ref, thr_ref, left_ref, right_ref,
                    leaf_ref[...] * lr_ref[...], out_ref, depth=depth,
                    leaf_width=leaf_width)


def _forest_quant_kernel(col_ref, lr_ref, scale_ref, init_ref, codes_ref,
                         feat_ref, thr_ref, left_ref, right_ref, leaf_ref,
                         out_ref, *, depth: int, leaf_width: int):
    """Quantized-storage variant of `_forest_kernel` (fp32 accumulation).

    Identical walk — thresholds arrive as exact small integers whatever
    their storage dtype (bin codes < 256), so the branch decisions are
    bit-identical to the fp32 kernel — plus one in-VMEM dequantization of
    the int8/bf16 leaf block (``astype(f32) * scale``) before the learning
    rate and the terminal one-hot gather, in the order the oracle rounds
    them, so the kernel matches `ref.forest_apply_quant_ref` bit-for-bit
    (asserted by the parity tests).  The model's VMEM working set shrinks
    4x (int8) / 2x (bf16) on the leaf tensor — the traversal is
    memory-bound on exactly that tensor.
    """
    leaf = leaf_ref[...].astype(jnp.float32) * scale_ref[...]
    _walk_and_place(col_ref[pl.program_id(1)], init_ref, codes_ref,
                    feat_ref, thr_ref, left_ref, right_ref,
                    leaf * lr_ref[...], out_ref, depth=depth,
                    leaf_width=leaf_width)


def _traverse_call(kernel, out_col, lr_row, scale_rows, F_init, codes, feat,
                   thr, left, right, leaf, *, name: str, row_tile: int,
                   interpret: bool):
    """Shared ``pallas_call`` of both traversal kernels.

    The per-tree output column is scalar-prefetched into SMEM and read at
    the tree's grid index; node tensors and the per-tree scale rows get a
    unit middle axis so their (1, N) / (1, W) blocks are legal under
    Mosaic's (8, 128) tiling rule.
    """
    n_pad, m_pad = codes.shape
    n_trees, node_pad = feat.shape
    l_pad, w_pad = leaf.shape[1], leaf.shape[2]
    d_pad = F_init.shape[1]
    assert n_pad % row_tile == 0 and l_pad == node_pad
    assert node_pad < 2 ** 24                     # exact f32 pointers
    per_tree = lambda width: pl.BlockSpec(  # noqa: E731
        (None, 1, width), lambda r, t, col: (t, 0, 0))
    rows = lambda width: pl.BlockSpec(  # noqa: E731
        (row_tile, width), lambda r, t, col: (r, 0))
    node3 = lambda x: x.reshape(n_trees, 1, node_pad)   # noqa: E731
    scale = [] if scale_rows is None else [scale_rows]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // row_tile, n_trees),
        in_specs=[pl.BlockSpec((1, w_pad), lambda r, t, col: (0, 0))]
        + [per_tree(w_pad)] * len(scale)
        + [rows(d_pad), rows(m_pad)] + [per_tree(node_pad)] * 4
        + [pl.BlockSpec((None, l_pad, w_pad), lambda r, t, col: (t, 0, 0))],
        out_specs=rows(d_pad))
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, name=name,
        out_shape=jax.ShapeDtypeStruct((n_pad, d_pad), jnp.float32),
        interpret=interpret,
    )(out_col, lr_row, *scale, F_init, codes, node3(feat), node3(thr),
      node3(left), node3(right), leaf)


@functools.partial(
    jax.jit,
    static_argnames=("depth", "leaf_width", "row_tile", "interpret"))
def forest_traverse_pallas(lr_row: jax.Array, out_col: jax.Array,
                           F_init: jax.Array, codes: jax.Array,
                           feat: jax.Array, thr: jax.Array,
                           left: jax.Array, right: jax.Array,
                           leaf: jax.Array,
                           *, depth: int, leaf_width: int,
                           row_tile: int = 256,
                           interpret: bool) -> jax.Array:
    """Raw kernel entry (padded inputs required — use `ops.forest_apply`).

    Args:
      lr_row:  (1, W) float32 learning rate repeated across the leaf width.
      out_col: (T,) int32 starting output column per tree (scalar-prefetched
               into SMEM).
      F_init:  (n, D) float32 initial scores, accumulated in place per tree.
      codes:   (n, M) int32 binned features.  n % row_tile == 0.
      feat, thr, left, right: (T, N) int32 node tensors; terminal nodes
               self-loop (left == right == own id); padded node slots are
               never reachable from node 0.
      leaf:    (T, N, W) float32 node-indexed leaf blocks (same padded node
               axis as feat); columns beyond ``leaf_width`` must be zero.
    Returns:
      (n, D) float32 scores ``F_init + lr * sum_t tree_t(codes)``.
    """
    assert leaf.shape[2] >= leaf_width
    kernel = functools.partial(_forest_kernel, depth=depth,
                               leaf_width=leaf_width)
    return _traverse_call(kernel, out_col, lr_row, None, F_init, codes, feat,
                          thr, left, right, leaf,
                          name="forest_traverse_pallas", row_tile=row_tile,
                          interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("depth", "leaf_width", "row_tile", "interpret"))
def forest_traverse_quant_pallas(lr_row: jax.Array, out_col: jax.Array,
                                 scale_rows: jax.Array, F_init: jax.Array,
                                 codes: jax.Array, feat: jax.Array,
                                 thr: jax.Array, left: jax.Array,
                                 right: jax.Array, leaf: jax.Array,
                                 *, depth: int, leaf_width: int,
                                 row_tile: int = 256,
                                 interpret: bool) -> jax.Array:
    """Quantized raw kernel entry (padded inputs — use
    `ops.forest_apply_quant`).

    Same grid/specs as `forest_traverse_pallas` plus per-tree dequant
    scales:

      scale_rows: (T, 1, W) float32 — each tree's dequant scale repeated
               across the leaf width (all-ones for bfloat16 leaves).
      leaf:    (T, N, W) int8 or bfloat16 node-indexed leaf blocks,
               dequantized in VMEM; fp32 accumulation throughout.
      thr:     (T, N) int32 bin-code thresholds (uint8 storage is widened
               by the wrapper — the walk compares exact small integers).
    """
    assert leaf.shape[2] >= leaf_width
    kernel = functools.partial(_forest_quant_kernel, depth=depth,
                               leaf_width=leaf_width)
    return _traverse_call(kernel, out_col, lr_row, scale_rows, F_init, codes,
                          feat, thr, left, right, leaf,
                          name="forest_traverse_quant_pallas",
                          row_tile=row_tile, interpret=interpret)
