"""Pallas TPU kernel: batched path-dependent TreeSHAP on a PackedForest.

Explanation serving is a *heavier* cousin of the traversal kernel
(`predict_kernel.py`): instead of walking each row to one leaf, every
root-to-leaf path of the tree contributes a Shapley term to every row — the
same "one thread block per (row tile, tree)" decomposition GPUTreeShap
(Mitchell et al., 2022) uses, mapped onto the TPU's MXU/VPU split:

  * slot gathers are one-hot matmuls on the MXU: for path slot ``s`` the
    (M, L) one-hot of ``slot_feat[:, s]`` pulls each path's split feature
    for the whole row tile in a single (TN, M) x (M, L) contraction;
  * the EXTEND/UNWIND polynomial algebra (prefix/suffix products of
    ``(z_j + o_j x)`` and the per-slot convolution Ψ_s) is unrolled
    element-wise VPU work over (TN, L) planes — `ref.path_unwind_psis`, the
    *same function* the jnp oracle runs, so the two are bit-identical by
    construction;
  * the leaf reduction contracts the ``lr``-scaled leaf block and the
    output-column placement is an exact 0/1 contraction, as in the
    traversal kernel.

Path metadata arrives pre-packed per (tree, leaf, slot) by
`repro.explain.paths.build_path_pack`: merged unique-feature conditions
(``o = lo < code <= hi``), cover-ratio zero-fractions ``z``, with inert
padding slots (``o = z = 1`` — exactly invariant null players).  Slot
tensors are stored slot-major ``(T, D_pad, L)`` so the lane axis is the
leaf axis (L = 2^depth >= 8 after padding) and the tiny slot axis sits on
sublanes.

Grid = ``(row_tiles, trees)``; the (TN, M, D_out) output block accumulates
across the sequential tree axis (init at t == 0, then ``+=`` each tree's
contribution — the oracle's scan order).  VMEM working set per step: codes
tile (TN x M x 4B), D x one-hot planes (M x L), the poly planes
(~D^2 x TN x L), the (TN, M, L) scatter plane, the (TN*M, W) leaf
contraction and the (TN, M, D_out) out tile — `ops.tree_shap` picks TN so
the out tile stays near 2 MiB (TN = 8 at M = 128, d = 512: ~9 MB in all,
inside 16 MB VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import path_unwind_psis


_HI = jax.lax.Precision.HIGHEST    # fp32 operands: no single bf16 MXU pass


def _dot(a, b):
    return jax.lax.dot_general(a, b, dimension_numbers=(((1,), (0,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _shap_kernel(col_ref, lr_ref, codes_ref, sf_ref, lo_ref, hi_ref,
                 z_ref, leaf_ref, out_ref, *, depth: int, leaf_width: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    codes = codes_ref[...].astype(jnp.float32)             # (TN, M)
    tn, m_pad = codes.shape
    l_pad = leaf_ref.shape[0]

    # Per-slot one-fractions via one-hot feature gathers (exact selections);
    # slot rows are (1, L) with the leaf axis on lanes.
    o_slots, z_slots, f_ohs = [], [], []
    for s in range(depth):
        f_oh = (jax.lax.broadcasted_iota(jnp.int32, (m_pad, l_pad), 0)
                == sf_ref[s:s + 1, :]).astype(jnp.float32)  # (M, L)
        c_s = _dot(codes, f_oh)                            # (TN, L) codes at
        o_s = ((c_s > lo_ref[s:s + 1, :].astype(jnp.float32))  # each split
               & (c_s <= hi_ref[s:s + 1, :].astype(jnp.float32))
               ).astype(jnp.float32)
        o_slots.append(o_s)
        z_slots.append(z_ref[s:s + 1, :])
        f_ohs.append(f_oh)

    # EXTEND/UNWIND — shared with the oracle, so bit-identical.
    psis = path_unwind_psis(o_slots, z_slots)

    # Scatter slots onto the feature axis: A[n, f, l] has at most one
    # non-zero slot per (leaf, feature) — an exact sum of D selection planes.
    A = None
    for s in range(depth):
        contrib_s = (o_slots[s] - z_slots[s]) * psis[s]    # (TN, L)
        term = contrib_s[:, None, :] * f_ohs[s][None, :, :]
        A = term if A is None else A + term                # (TN, M, L)

    # `ref.scaled_leaves`: lr arrives as a (1, W) row, not an SMEM scalar
    # that the interpreter's XLA would hoist out of the matmul.
    leaf = leaf_ref[...] * lr_ref[...]
    res = _dot(A.reshape(tn * m_pad, l_pad), leaf)         # (TN*M, W)

    # Placement matrix: leaf-block column i lands in output column col + i.
    col = col_ref[t]
    w_pad, d_pad = res.shape[1], out_ref.shape[2]
    rows = jax.lax.broadcasted_iota(jnp.int32, (w_pad, d_pad), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (w_pad, d_pad), 1)
    place = ((rows < leaf_width) & (rows + col == cols)).astype(jnp.float32)
    out_ref[...] += _dot(res, place).reshape(tn, m_pad, d_pad)


@functools.partial(
    jax.jit,
    static_argnames=("depth", "leaf_width", "d_pad", "row_tile", "interpret"))
def shap_pallas(lr_row: jax.Array, out_col: jax.Array, codes: jax.Array,
                slot_feat: jax.Array, slot_lo: jax.Array, slot_hi: jax.Array,
                slot_z: jax.Array, leaf: jax.Array, *, depth: int,
                leaf_width: int, d_pad: int, row_tile: int,
                interpret: bool) -> jax.Array:
    """Raw kernel entry (padded inputs required — use `ops.tree_shap`).

    Args:
      lr_row:  (1, W) float32 learning rate repeated across the leaf width.
      out_col: (T,) int32 starting output column per tree (scalar-prefetched
               into SMEM).
      codes:   (n, M) int32 binned features.  n % row_tile == 0.
      slot_feat, slot_lo, slot_hi: (T, D_pad, L) int32 slot-major path
               conditions, D_pad >= depth (extra slot rows are never read);
               padding slots/leaves carry feat = -1, lo = -1 (o = 1).
      slot_z:  (T, D_pad, L) float32 zero-fractions (1 on padding).
      leaf:    (T, L, W) float32 leaf blocks; columns beyond ``leaf_width``
               must be zero padding.
      d_pad:   padded output dimension (>= out_col + leaf_width everywhere).
    Returns:
      (n, M, d_pad) float32 per-(row, feature, output) SHAP values,
      ``lr``-scaled and summed over trees (base values NOT included).
    """
    n_pad, m_pad = codes.shape
    n_trees, d_slot_pad, l_pad = slot_feat.shape
    w_pad = leaf.shape[2]
    # The leaf axis is the packed forest's node axis: sparse-topology trees
    # may carry fewer than 2^depth slots, so only shape agreement is asserted.
    assert n_pad % row_tile == 0 and d_slot_pad >= depth
    assert leaf.shape[1] == l_pad
    slot_spec = pl.BlockSpec((None, d_slot_pad, l_pad),
                             lambda r, t, col: (t, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pad // row_tile, n_trees),
        in_specs=[
            pl.BlockSpec((1, w_pad), lambda r, t, col: (0, 0)),
            pl.BlockSpec((row_tile, m_pad), lambda r, t, col: (r, 0)),
            slot_spec, slot_spec, slot_spec, slot_spec,
            pl.BlockSpec((None, l_pad, w_pad), lambda r, t, col: (t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((row_tile, m_pad, d_pad),
                               lambda r, t, col: (r, 0, 0)))
    return pl.pallas_call(
        functools.partial(_shap_kernel, depth=depth, leaf_width=leaf_width),
        name="shap_pallas",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_pad, m_pad, d_pad), jnp.float32),
        interpret=interpret,
    )(out_col, lr_row, codes, slot_feat, slot_lo, slot_hi, slot_z, leaf)
