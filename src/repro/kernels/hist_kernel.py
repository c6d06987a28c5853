"""Pallas TPU kernels: gradient histogram accumulation (the GBDT hot spot).

**Partitioned tiles kernel** (`hist_tiles_pallas`) — the node-partitioned
engine's hot loop.  `ops.histogram_splits_level` gathers rows into
node-contiguous tiles (each tile belongs to exactly ONE node; per-node row
ranges are padded to the tile size), so the one-hot space per row tile is
only ``n_bins`` wide:

    node_hist[f, tile_node[t]] (+)= fold(onehot(bin_f)^T @ parts_tile)
                                    (TN, B)^T bf16    (TN, P) bf16

Grid = (features, tiles), tiles innermost and sequential.  The tile->node
map is scalar-prefetched into SMEM and drives the output index map, so the
tiles of one node run in one block of steps: the node's first tile writes
its dot to a VMEM accumulator, each later tile adds its dot, the node's
last tile folds the accumulator into the node's ``(B, C)`` output block,
and the block goes back to HBM once, when the run ends.  The kernel writes
node histograms, never per-tile ones.  Per-level FLOPs are O(n * m * c)
regardless of depth.

Precision: one bf16 MXU pass, exact for float32 statistics.  The one-hot
is 0 or 1, exact in bf16; `stat_parts` splits each float32 statistic into
three bf16 parts ``hi + mid + lo == x`` laid side by side in the lanes
(``[0, c)``, ``[c, 2c)``, ``[2c, 3c)``), so every product of the pass is
exact and the MXU adds them in float32.  ``fold`` adds each channel's
three lane groups into lanes ``[0, c)`` with two lane rolls, once per node.
These are the three nonzero products of a ``Precision.HIGHEST``
contraction (six bf16 passes, three of which multiply the one-hot's zero
mid and lo parts), in one pass over ``P = round_up(3c, 128)`` lanes: 128
for c <= 42.  ``hist_dtype="bfloat16"`` feeds one rounded part.
VMEM per step: onehot (B x TN x 2B) + parts (TN x P x 2B) + accumulator
(B x P x 4B) + out (B x C x 4B) — ~0.4 MB at TN=256, B=256, P=C=128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import tracing as TR


HIST_DTYPES = ("float32", "bfloat16")


def hist_parts(hist_dtype: str) -> int:
    """bf16 parts per float32 statistic that the tiles kernel contracts:
    3 for ``"float32"`` (an exact split, `stat_parts`), 1 for
    ``"bfloat16"`` (the statistic rounded to bf16)."""
    if hist_dtype not in HIST_DTYPES:
        raise ValueError(f"unknown hist_dtype {hist_dtype!r}; "
                         f"expected one of {HIST_DTYPES}")
    return 3 if hist_dtype == "float32" else 1


def parts_lanes(n_channels: int, n_parts: int, lane_pad: int) -> int:
    """Lane width of the kernel's stats operand: ``n_parts`` groups of
    ``n_channels`` lanes, padded to a multiple of ``lane_pad``."""
    return -(-n_parts * n_channels // lane_pad) * lane_pad


_HI_MASK = 0xFFFF0000                     # sign, exponent, top 7 mantissa bits


def stat_parts(stats: jax.Array, *, n_parts: int,
               lane_pad: int) -> jax.Array:
    """(S, c) statistics -> (S, `parts_lanes`) bfloat16 kernel operand.

    Lane group ``k`` (lanes ``[k c, (k + 1) c)``) holds part ``k``; the
    lanes past ``n_parts * c`` are zero.  With one part it is the
    statistic rounded to bf16.  With three, ``hi`` and ``mid`` are the
    residual truncated to its top 8 significant bits (a bit mask, so no
    compiler can fold the float32 -> bf16 -> float32 round trip away) and
    ``lo`` is what is left: 24 significant bits in three fields of 8, so
    ``hi + mid + lo == x`` bit for bit wherever ``lo`` is a normal bf16
    (``|x|`` above about 1e-31); below that ``lo`` is subnormal, lost as
    in ``Precision.HIGHEST``'s own split.
    """
    r = stats.astype(jnp.float32)
    parts = []
    for _ in range(n_parts - 1):
        hi = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(r, jnp.uint32)
            & jnp.uint32(_HI_MASK), jnp.float32)
        parts.append(hi)
        r = r - hi                                         # exact
    parts.append(r)
    s, c = stats.shape
    pad = parts_lanes(c, n_parts, lane_pad) - n_parts * c
    parts.append(jnp.zeros((s, pad), jnp.float32))
    return jnp.concatenate(parts, axis=1).astype(jnp.bfloat16)


def _hist_tiles_kernel(node_ref, codes_ref, parts_ref, out_ref, acc_ref, *,
                       n_bins: int, n_channels: int, n_parts: int):
    t = pl.program_id(1)
    t_end = pl.num_programs(1) - 1
    node = node_ref[t]
    first = (t == 0) | (node != node_ref[jnp.maximum(t - 1, 0)])
    last = (t == t_end) | (node != node_ref[jnp.minimum(t + 1, t_end)])
    code = codes_ref[...].astype(jnp.int32)               # (1, TN)
    tn = code.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_bins, tn), 0)
    onehot_t = (code == rows).astype(jnp.bfloat16)        # (B, TN), exact
    # One bf16 MXU pass: each product selects a bf16 part exactly, and the
    # MXU accumulates in float32.
    dot = jax.lax.dot_general(
        onehot_t, parts_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # (B, P)
    # A select, not a branch: the accumulator's load and add then overlap
    # the matmul (a pl.when pair measured 5% slower per step on a v5e).  On
    # a node's first tile it holds the last node's sums, not selected.
    acc = jnp.where(first, dot, acc_ref[...] + dot)
    acc_ref[...] = acc

    @pl.when(last)
    def _fold():
        # Once per node: lane i gathers its channel's parts, (hi + mid) +
        # lo, as rolling by P - k c brings lane i + k c to lane i.  Lanes
        # past the channels then hold sums of other groups: zeroed.
        p, lanes = acc.shape[1], out_ref.shape[-1]
        hist = acc
        for k in range(1, n_parts):
            hist = hist + pltpu.roll(acc, p - k * n_channels, 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (n_bins, lanes), 1)
        out_ref[...] = jnp.where(lane < n_channels, hist[:, :lanes], 0.0)


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "n_channels", "n_parts",
                     "lane_pad", "row_tile", "interpret"))
def hist_tiles_pallas(tile_node: jax.Array, codes_t: jax.Array,
                      parts: jax.Array, *, n_nodes: int, n_bins: int,
                      n_channels: int, n_parts: int, lane_pad: int,
                      row_tile: int = 256, interpret: bool) -> jax.Array:
    """Raw node-histogram kernel entry (node-contiguous gathered inputs
    required — use `ops.histogram_splits_level` / `ops.node_histogram`).

    Args:
      tile_node: (S // row_tile,) int32 node of each tile, sorted, every
               node in ``[0, n_nodes)`` present at least once (a node's
               block is written only by its own tiles).
      codes_t: (m, S) transposed bin codes in partition order, S a multiple
               of ``row_tile``; every tile of ``row_tile`` rows belongs to a
               single tree node (padding rows carry zero stats).
      parts:   (S, `parts_lanes`) bfloat16 ``stat_parts`` of the (S,
               n_channels) statistics in the same order, ``n_parts`` =
               `hist_parts` of the histogram dtype: 3 exact parts for
               float32 statistics, 1 rounded part for ``"bfloat16"``
               (only the gradient channels round, ~2^-8 relative; the count
               channel is exact for integer weights < 256).
    Returns:
      (m, n_nodes, n_bins, C) float32 node histograms, C = ``n_channels``
      padded to a multiple of ``lane_pad``, lanes past the channels zero:
      each node's tiles summed in tile order.
    """
    m, s = codes_t.shape
    p = parts.shape[1]
    assert s % row_tile == 0 and parts.dtype == jnp.bfloat16
    assert p == parts_lanes(n_channels, n_parts, lane_pad), (p, n_channels)
    lanes = parts_lanes(n_channels, 1, lane_pad)
    n_tiles = s // row_tile
    assert tile_node.shape == (n_tiles,)
    TR.record_hist_level(m, n_tiles, n_nodes, n_parts, p)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, n_tiles),
        in_specs=[
            pl.BlockSpec((None, 1, row_tile), lambda f, t, node: (f, 0, t)),
            pl.BlockSpec((row_tile, p), lambda f, t, node: (t, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, n_bins, lanes),
                               lambda f, t, node: (f, node[t], 0, 0)),
        scratch_shapes=[pltpu.VMEM((n_bins, p), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_hist_tiles_kernel, n_bins=n_bins,
                          n_channels=n_channels, n_parts=n_parts),
        grid_spec=grid_spec,
        name="hist_tiles_pallas",
        out_shape=jax.ShapeDtypeStruct((m, n_nodes, n_bins, lanes),
                                       jnp.float32),
        # A node's block is revisited by consecutive tiles: the tile axis
        # must run in order on one core.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tile_node.astype(jnp.int32), codes_t.reshape(m, 1, s), parts)
