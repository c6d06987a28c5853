"""Pallas TPU kernel: split-scan — cumulative stats + best-split arg-max.

Consumes the histogram kernel's *native* ``(m, n_nodes * n_bins, C)`` layout
(no host-side transpose between the two kernels) and produces, per tree node,
the best ``(feature, bin)`` split under the paper's eq. (4) score

    S(R) = ||sum_{i in R} g_i||^2 / (|R| + lambda),
    gain = 0.5 * (S(R_l) + S(R_r) - S(R_parent)).

Grid = ``(n_nodes, m_tiles)``; each step loads one feature tile of one node's
histogram, computes the cumulative left/right statistics along the bin axis
(one lower-triangular matmul per feature on the MXU — Mosaic has no cumsum),
scores every candidate threshold on the VPU, and folds its local arg-max into
the per-node output block.  The output block for a node is revisited across the
sequential feature-tile axis — the canonical Pallas accumulation pattern
(init at ``ft == 0``, strict ``>`` keeps the *first* maximum, matching
``jnp.argmax`` tie-breaking over the flattened ``(m, B)`` axis).

Channel layout: ``C`` is the lane-padded stats width; the real channels are
``[0 .. n_channels-2]`` sketched-gradient sums and ``[n_channels-1]`` counts,
padding channels are zero.  The squared norm of the gradient block is computed
as ``sum_c s_c^2 - count^2`` so no lane slicing is needed inside the kernel.

VMEM working set per step: hist tile (MT x B x C x 4B, double-buffered) +
the (B x B) triangle + one feature's (B x C) prefix sums and (B x 1) score
columns — with the default MT=8, B=256, C=128 that is ~2 x 1 MB + 0.5 MB,
comfortably inside 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")   # python literal: jnp constants may not be captured


def _split_scan_kernel(params_ref, mask_ref, hist_ref, gain_ref, idx_ref, *,
                       n_bins: int, n_channels: int, m_tile: int):
    ft = pl.program_id(1)
    lam = params_ref[0, 0]
    min_data = params_ref[0, 1]
    c_pad = hist_ref.shape[2]

    # Prefix sums along the bin (sublane) axis as one lower-triangular
    # matmul per feature: Mosaic has no cumsum, and HIGHEST keeps the fp32
    # sums from being truncated to a single bf16 MXU pass.
    tri = (jax.lax.broadcasted_iota(jnp.int32, (n_bins, n_bins), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (n_bins, n_bins), 1)
           ).astype(jnp.float32)
    # One-hot lane mask of the count channel (padding lanes are all-zero).
    cvec = (jax.lax.broadcasted_iota(jnp.int32, (1, c_pad), 1)
            == n_channels - 1).astype(jnp.float32)
    bins = jax.lax.broadcasted_iota(jnp.int32, (n_bins, 1), 0)
    big = jnp.int32(m_tile * n_bins)

    best_g = jnp.full((1, 1), NEG_INF, jnp.float32)
    best_i = jnp.zeros((1, 1), jnp.int32)
    for f in range(m_tile):
        csum = jax.lax.dot_general(                        # (B, C) left stats
            tri, hist_ref[f], dimension_numbers=(((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        cl = jnp.sum(csum * cvec, axis=1, keepdims=True)   # (B, 1) counts
        sl_num = jnp.sum(csum * csum, axis=1, keepdims=True) - cl * cl
        totals = csum[n_bins - 1:n_bins, :]                # (1, C) node total
        ct = jnp.sum(totals * cvec, axis=1, keepdims=True)  # (1, 1)
        tot_num = jnp.sum(totals * totals, axis=1, keepdims=True) - ct * ct
        rdiff = totals - csum                              # right stats
        cr = ct - cl
        sr_num = jnp.sum(rdiff * rdiff, axis=1, keepdims=True) - cr * cr

        s_left = sl_num / (cl + lam)
        s_right = sr_num / (cr + lam)
        s_parent = tot_num / (ct + lam)
        gain = 0.5 * (s_left + s_right - s_parent)         # (B, 1)
        legal = (bins < n_bins - 1) & (cl >= min_data) & (cr >= min_data)
        legal &= mask_ref[f:f + 1, :] > 0.0                # (1, 1) broadcast
        gain = jnp.where(legal, gain, NEG_INF)

        # First maximum of this feature (jnp.argmax tie-breaking); features
        # are visited in index order, so strict ``>`` keeps the first one.
        g_f = jnp.max(gain, axis=0, keepdims=True)         # (1, 1)
        i_f = jnp.min(jnp.where(gain == g_f, bins + f * n_bins, big),
                      axis=0, keepdims=True)
        better = g_f > best_g
        best_g = jnp.where(better, g_f, best_g)
        best_i = jnp.where(better, i_f, best_i)
    best_i = best_i + ft * (m_tile * n_bins)               # flat (feat, bin)

    @pl.when(ft == 0)
    def _init():
        gain_ref[...] = jnp.full(gain_ref.shape, NEG_INF, jnp.float32)
        idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)

    cur_g = gain_ref[...]                                  # (1, lane_pad)
    better = best_g > cur_g
    gain_ref[...] = jnp.where(better, best_g, cur_g)
    idx_ref[...] = jnp.where(better, best_i, idx_ref[...])


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "n_channels", "m_tile", "lane_pad",
                     "interpret"))
def split_scan_pallas(hist: jax.Array, params: jax.Array, mask: jax.Array, *,
                      n_nodes: int, n_bins: int, n_channels: int,
                      m_tile: int = 8, lane_pad: int = 8,
                      interpret: bool):
    """Raw kernel entry (padded inputs required — use `ops.split_scan`).

    Args:
      hist:   (m_pad, n_nodes * n_bins, C) float32, m_pad % m_tile == 0;
              channels beyond ``n_channels`` must be zero padding.
      params: (1, 2) float32 [lambda, min_data_in_leaf] (SMEM scalars).
      mask:   (m_pad, 1) float32; 0 disables a feature (colsample / padding).
    Returns:
      (best_gain, best_idx): each (n_nodes, lane_pad) with the per-node result
      broadcast across lanes — callers read column 0.  ``best_idx`` encodes
      ``feature * n_bins + bin``; ``best_gain`` is -inf when no legal split.
    """
    m_pad, nb_total, c = hist.shape
    assert m_pad % m_tile == 0 and nb_total == n_nodes * n_bins
    grid = (n_nodes, m_pad // m_tile)

    gain, idx = pl.pallas_call(
        functools.partial(_split_scan_kernel, n_bins=n_bins,
                          n_channels=n_channels, m_tile=m_tile),
        name="split_scan_pallas",
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((m_tile, 1), lambda node, ft: (ft, 0)),
            pl.BlockSpec((m_tile, n_bins, c), lambda node, ft: (ft, node, 0)),
        ],
        # A unit middle axis makes the per-node (1, lane_pad) block legal
        # under Mosaic's (8, 128) tiling rule.
        out_specs=[
            pl.BlockSpec((None, 1, lane_pad), lambda node, ft: (node, 0, 0)),
            pl.BlockSpec((None, 1, lane_pad), lambda node, ft: (node, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_nodes, 1, lane_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_nodes, 1, lane_pad), jnp.int32),
        ],
        interpret=interpret,
    )(params, mask, hist)
    return gain[:, 0], idx[:, 0]
