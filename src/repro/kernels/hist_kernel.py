"""Pallas TPU kernels: gradient histogram accumulation (the GBDT hot spot).

Two generations:

**Direct kernel** (`histogram_pallas`) — TPU adaptation of Py-Boost's CUDA
atomic scatter histograms: each grid step builds the one-hot matrix of the
combined ``(node, bin)`` index for a row tile and contracts it with the
statistics tile **on the MXU**:

    hist[f, nb_chunk] += onehot(node*B + bin_f - chunk_off)^T  @  stats_tile
                         (TN, NBC)                                (TN, C)

Grid = (features, nb_chunks, row_tiles); the output block for a given
(feature, chunk) is revisited across the sequential row-tile axis, which is the
canonical Pallas accumulation pattern (zero-init at t==0).  VMEM working set per
step: onehot (TN x NBC x 4B) + stats (TN x C) + out (NBC x C) — with the default
TN=256, NBC=2048, C<=128 that is ~2.3 MB, comfortably inside 16 MB VMEM while
keeping MXU-aligned contraction dims (TN multiple of 8, C padded to lanes by
`ops.histogram`).  Its one-hot space spans ``n_nodes * n_bins`` per row, so
per-level FLOPs grow with the node count — O(n * m * c * 2^l) at level ``l``.

**Partitioned tiles kernel** (`hist_tiles_pallas`) — the node-partitioned
engine's hot loop.  `ops.histogram_splits_level` gathers rows into
node-contiguous tiles (each tile belongs to exactly ONE node; per-node row
ranges are padded to the tile size), so the one-hot space per row tile is
only ``n_bins`` wide:

    node_hist[f, tile_node[t]] (+)= onehot(bin_f)^T @ stats_tile
                                    (TN, B)^T         (TN, C)

Grid = (features, tiles), tiles innermost and sequential.  The tile->node
map is scalar-prefetched into SMEM and drives the output index map, so the
tiles of one node revisit that node's ``(B, C)`` block in one run: the
node's first tile writes its dot, each later tile adds its dot to the
block, and the block goes back to HBM once, when the run ends.  The kernel writes node
histograms, never per-tile ones.  Per-level FLOPs are O(n * m * c)
regardless of depth.  VMEM per step: onehot (TN x B x 4B) + stats (TN x C)
+ out (B x C) — ~0.5 MB at TN=256, B=256, C=128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime import tracing as TR


def _hist_kernel(codes_ref, node_ref, stats_ref, out_ref, *, n_bins: int,
                 nb_chunk: int):
    t = pl.program_id(2)
    nb = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    code = codes_ref[...].astype(jnp.int32)               # (1, TN)
    seg = node_ref[...].astype(jnp.int32) * n_bins + code  # (1, TN)
    rel = seg - nb * nb_chunk
    tn = code.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (nb_chunk, tn), 0)
    onehot_t = (rel == rows).astype(jnp.float32)          # (NBC, TN)
    out_ref[...] += jax.lax.dot_general(
        onehot_t, stats_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)               # (NBC, C)


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "row_tile", "nb_chunk", "interpret"))
def histogram_pallas(codes_t: jax.Array, node_pos: jax.Array, stats: jax.Array,
                     *, n_nodes: int, n_bins: int, row_tile: int = 256,
                     nb_chunk: int = 2048, interpret: bool) -> jax.Array:
    """Raw kernel entry (padded inputs required — use `ops.histogram`).

    Args:
      codes_t: (m, n) transposed bin codes (feature-major for contiguous tiles).
      node_pos: (n,) int32; stats: (n, C) float32.  n % row_tile == 0.
    Returns:
      (m, n_nodes * n_bins, C) float32 histograms.
    """
    m, n = codes_t.shape
    c = stats.shape[1]
    nb_total = n_nodes * n_bins
    nb_chunk = min(nb_chunk, nb_total)
    assert nb_total % nb_chunk == 0 and n % row_tile == 0
    grid = (m, nb_total // nb_chunk, n // row_tile)

    # Mosaic blocks the last two dims in (8, 128) tiles or whole: a unit
    # middle axis makes every (1, row_tile) code / node block legal.
    return pl.pallas_call(
        functools.partial(_hist_kernel, n_bins=n_bins, nb_chunk=nb_chunk),
        name="histogram_pallas",
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, 1, row_tile), lambda f, nb, t: (f, 0, t)),
            pl.BlockSpec((1, row_tile), lambda f, nb, t: (0, t)),
            pl.BlockSpec((row_tile, c), lambda f, nb, t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((None, nb_chunk, c),
                               lambda f, nb, t: (f, nb, 0)),
        out_shape=jax.ShapeDtypeStruct((m, nb_total, c), jnp.float32),
        interpret=interpret,
    )(codes_t.reshape(m, 1, n), node_pos.reshape(1, n), stats)


HIST_DTYPES = ("float32", "bfloat16")


def _hist_tiles_kernel(node_ref, codes_ref, stats_ref, out_ref, *,
                       n_bins: int, compute_dtype, precision):
    t = pl.program_id(1)
    first = (t == 0) | (node_ref[t] != node_ref[jnp.maximum(t - 1, 0)])
    code = codes_ref[...].astype(jnp.int32)               # (1, TN)
    tn = code.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (n_bins, tn), 0)
    onehot_t = (code == rows).astype(compute_dtype)       # (B, TN)
    dot = jax.lax.dot_general(
        onehot_t, stats_ref[...].astype(compute_dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        precision=precision,
        preferred_element_type=jnp.float32)               # (B, C)
    # A select, not a branch: the block's load and add then overlap the
    # matmul (a pl.when pair measured 5% slower per step on a v5e).  On a
    # node's first tile the block holds stale data, which is not selected.
    out_ref[...] = jnp.where(first, dot, out_ref[...] + dot)


@functools.partial(
    jax.jit,
    static_argnames=("n_nodes", "n_bins", "row_tile", "hist_dtype",
                     "interpret"))
def hist_tiles_pallas(tile_node: jax.Array, codes_t: jax.Array,
                      stats: jax.Array, *, n_nodes: int, n_bins: int,
                      row_tile: int = 256, hist_dtype: str = "float32",
                      interpret: bool) -> jax.Array:
    """Raw node-histogram kernel entry (node-contiguous gathered inputs
    required — use `ops.histogram_splits_level` / `ops.node_histogram`).

    Args:
      tile_node: (S // row_tile,) int32 node of each tile, sorted, every
               node in ``[0, n_nodes)`` present at least once (a node's
               block is written only by its own tiles).
      codes_t: (m, S) transposed bin codes in partition order, S a multiple
               of ``row_tile``; every tile of ``row_tile`` rows belongs to a
               single tree node (padding rows carry zero stats).
      stats:   (S, C) float32 statistics in the same order.
      hist_dtype: MXU input dtype for the one-hot contraction.
               ``"bfloat16"`` halves the stats-operand bytes feeding the MXU
               (the sketched gradient channel — exactly the traffic the
               paper's sketch already shrinks d -> k); accumulation stays
               float32 (``preferred_element_type`` and the float32 output
               block), and the one-hot side is exact in either dtype, so
               only the gradient channels round (~2^-8 relative); the count
               channel is exact for integer weights < 256.  The
               subtraction-drift bound under bf16 is asserted in
               tests/test_hist_engine.py next to the fp32 bound.
    Returns:
      (m, n_nodes, n_bins, C) float32 node histograms: each node's tiles
      summed in tile order.
    """
    m, s = codes_t.shape
    c = stats.shape[1]
    assert s % row_tile == 0
    if hist_dtype not in HIST_DTYPES:
        raise ValueError(f"unknown hist_dtype {hist_dtype!r}; "
                         f"expected one of {HIST_DTYPES}")
    bf16 = hist_dtype == "bfloat16"
    compute_dtype = jnp.bfloat16 if bf16 else jnp.float32
    # fp32 stats must not be truncated to one bf16 MXU pass.
    precision = None if bf16 else jax.lax.Precision.HIGHEST
    n_tiles = s // row_tile
    assert tile_node.shape == (n_tiles,)
    TR.record_hist_level(m, n_tiles, n_nodes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(m, n_tiles),
        in_specs=[
            pl.BlockSpec((None, 1, row_tile), lambda f, t, node: (f, 0, t)),
            pl.BlockSpec((row_tile, c), lambda f, t, node: (t, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, n_bins, c),
                               lambda f, t, node: (f, node[t], 0, 0)))
    return pl.pallas_call(
        functools.partial(_hist_tiles_kernel, n_bins=n_bins,
                          compute_dtype=compute_dtype, precision=precision),
        grid_spec=grid_spec,
        name="hist_tiles_pallas",
        out_shape=jax.ShapeDtypeStruct((m, n_nodes, n_bins, c), jnp.float32),
        # A node's block is revisited by consecutive tiles: the tile axis
        # must run in order on one core.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(tile_node.astype(jnp.int32), codes_t.reshape(m, 1, s), stats)
