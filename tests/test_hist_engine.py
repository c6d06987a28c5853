"""Node-partitioned histogram engine: partition invariants, sibling
subtraction, kernel/oracle parity, and end-to-end engine equivalence.

Tolerance contract (documented in docs/performance.md): the ``partition``
engine re-orders row summation only, so histograms match ``direct`` to
float32 accumulation noise (~1e-5 relative); the ``subtract`` engine derives
each larger sibling as ``parent − built``, whose cancellation error is
bounded by ``O(eps * ||parent||)`` per cell — 1e-3 absolute at test scales.
Split *decisions* are identical on all fixed seeds below (near-ties closer
than the drift bound could legally flip, which is why the legacy
kernel-vs-jnp e2e in test_gbdt_core.py pins the direct engine).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import histogram as H
from repro.core import tree as T
from repro.core.boosting import GBDTConfig, SketchBoost
from repro.data.pipeline import make_tabular
from repro.kernels import ops, ref


def _rand_problem(seed, n=400, m=6, B=16, d=3, depth=3, weights=None):
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(rng.integers(0, B, (n, m)).astype(np.uint8))
    G = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    Hd = jnp.ones((n, d), jnp.float32)
    w = (jnp.ones((n, 1), jnp.float32) if weights is None
         else jnp.asarray(weights, jnp.float32).reshape(n, 1))
    stats = jnp.concatenate([G * w, w], axis=1)
    return codes, stats, G, Hd


def _routed_state(codes, stats, depth, n_bins):
    """Grow a direct-engine tree and replay its routing to produce a
    realistic LevelState + node_pos sequence per level."""
    n = codes.shape[0]
    tree, _ = T.grow_tree(codes, stats, stats[:, :-1], jnp.ones_like(
        stats[:, :-1]), depth=depth, n_bins=n_bins, lam=1.0,
        use_kernel="jnp", hist_engine="direct")
    state = H.init_level_state(n)
    node_pos = jnp.zeros((n,), jnp.int32)
    out = [(state, node_pos)]
    for lvl in range(depth - 1):
        off = 2 ** lvl - 1
        feat = jax.lax.dynamic_slice(tree.feat, (off,), (2 ** lvl,))
        thr = jax.lax.dynamic_slice(tree.thr, (off,), (2 ** lvl,))
        bits = T.route_bits(codes, node_pos, feat, thr)
        node_pos = node_pos * 2 + bits
        state = H.advance_level_state(state, bits)
        out.append((state, node_pos))
    return out


# ---------------------------------------------------------------------------
# LevelState / radix partition invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_partition_state_invariants(seed):
    codes, stats, _, _ = _rand_problem(seed, n=300, depth=4)
    for lvl, (state, node_pos) in enumerate(
            _routed_state(codes, stats, 4, 16)):
        order = np.asarray(state.order)
        node_perm = np.asarray(state.node_perm)
        counts = np.asarray(state.counts)
        pos = np.asarray(node_pos)
        # order is a permutation; node_perm is sorted; counts match bincount.
        assert sorted(order.tolist()) == list(range(300))
        assert (np.diff(node_perm) >= 0).all()
        np.testing.assert_array_equal(
            counts, np.bincount(pos, minlength=2 ** lvl))
        # node_perm is node_pos gathered through the permutation.
        np.testing.assert_array_equal(node_perm, pos[order])


def test_partition_is_stable():
    """Within a node, rows keep their original dataset order."""
    codes, stats, _, _ = _rand_problem(3, n=200, depth=4)
    for state, _ in _routed_state(codes, stats, 4, 16):
        order = np.asarray(state.order)
        node_perm = np.asarray(state.node_perm)
        for c in np.unique(node_perm):
            seg = order[node_perm == c]
            assert (np.diff(seg) > 0).all()      # strictly increasing row ids


def test_smaller_children_selection():
    counts = jnp.asarray([3, 5, 7, 2, 4, 4], jnp.int32)
    side, is_built = H.smaller_children(counts)
    np.testing.assert_array_equal(np.asarray(side), [0, 1, 0])  # ties -> left
    np.testing.assert_array_equal(np.asarray(is_built),
                                  [True, False, False, True, True, False])


# ---------------------------------------------------------------------------
# jnp engine parity: partition / subtract vs direct histograms
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,weights", [(0, None), (1, None), (2, "sgb")])
def test_level_builders_match_direct(seed, weights):
    n, B, depth = 500, 16, 4
    rng = np.random.default_rng(seed + 100)
    w = None if weights is None else (rng.random(n) < 0.7).astype(np.float32)
    codes, stats, _, _ = _rand_problem(seed, n=n, B=B, depth=depth, weights=w)
    prev = None
    for lvl, (state, node_pos) in enumerate(
            _routed_state(codes, stats, depth, B)):
        n_nodes = 2 ** lvl
        direct = H.build_histograms_jnp(codes, node_pos, stats,
                                        n_nodes=n_nodes, n_bins=B)
        part = H.build_level_jnp(codes, stats, state, None,
                                 n_nodes=n_nodes, n_bins=B, subtract=False)
        np.testing.assert_allclose(np.asarray(part), np.asarray(direct),
                                   rtol=1e-5, atol=1e-4)
        sub = H.build_level_jnp(codes, stats, state, prev,
                                n_nodes=n_nodes, n_bins=B,
                                subtract=lvl > 0)
        np.testing.assert_allclose(np.asarray(sub), np.asarray(direct),
                                   rtol=1e-4, atol=1e-3)
        prev = sub


def test_subtract_count_channel_smaller_side_exact():
    """The directly-built (smaller) child's histogram is a pure re-ordered
    sum — its count channel with unit weights is integer-exact."""
    codes, stats, _, _ = _rand_problem(4, n=600, depth=4)
    levels = _routed_state(codes, stats, 4, 16)
    prev = None
    for lvl, (state, node_pos) in enumerate(levels):
        hist = H.build_level_jnp(codes, stats, state, prev,
                                 n_nodes=2 ** lvl, n_bins=16,
                                 subtract=lvl > 0)
        prev = hist
        counts = np.asarray(hist)[..., -1].sum(axis=2)     # (nodes, m)
        if lvl > 0:
            _, is_built = H.smaller_children(state.counts)
            built = np.asarray(is_built)
            exact = np.asarray(state.counts, np.float32)[built, None]
            np.testing.assert_array_equal(counts[built], np.broadcast_to(
                exact, counts[built].shape))


# ---------------------------------------------------------------------------
# Pallas tiles kernel vs oracle (bit parity) and fused level op
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,tn,B,c,tile_node,n_pad", [
    (3, 64, 8, 2, [0, 1], 0),
    (5, 32, 16, 4, [0, 0, 1, 1], 0),
    (2, 128, 32, 8, [0, 0, 1], 0),
    (4, 32, 16, 4, [0, 1, 2, 3, 4], 0),          # runs of length 1
    (3, 32, 8, 4, [0, 0, 1, 2, 2, 2, 2], 3),     # trailing all-padding run
    (2, 64, 16, 4, [0, 0, 0, 0], 0),             # single-node map
])
def test_hist_tiles_kernel_bit_matches_ref(m, tn, B, c, tile_node, n_pad):
    """The tiles kernel sums each node's tiles in that node's output block,
    bit for bit as the oracle folds them in tile order; the last ``n_pad``
    tiles carry zero stats, as the tile plan's trailing padding does."""
    tiles = len(tile_node)
    tile_node = jnp.asarray(tile_node, jnp.int32)
    n_nodes = int(tile_node[-1]) + 1
    ks = jax.random.split(jax.random.key(m * tn + tiles), 2)
    codes_t = jax.random.randint(ks[0], (m, tn * tiles), 0, B, jnp.int32)
    stats = jax.random.normal(ks[1], (tn * tiles, c), jnp.float32)
    stats = stats.at[tn * (tiles - n_pad):].set(0.0)
    from repro.kernels.hist_kernel import hist_tiles_pallas, stat_parts
    kw = dict(n_nodes=n_nodes, n_bins=B, n_channels=c, n_parts=3,
              lane_pad=8, row_tile=tn)
    parts = stat_parts(stats, n_parts=3, lane_pad=8)
    out_k = hist_tiles_pallas(tile_node, codes_t, parts, **kw,
                              interpret=True)
    out_r = ref.histogram_tiles_ref(tile_node, codes_t, parts, **kw)
    assert out_k.shape == (m, n_nodes, B, 8)
    np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))
    # The oracle is the per-node sum of the rows in its tiles; lanes past
    # the channels are zero.
    node_of_row = np.repeat(np.asarray(tile_node), tn)
    direct = H.build_histograms_jnp(codes_t.T, jnp.asarray(node_of_row),
                                    stats, n_nodes=n_nodes, n_bins=B)
    np.testing.assert_allclose(np.asarray(out_r)[..., :c],
                               np.asarray(direct).transpose(1, 0, 2, 3),
                               rtol=1e-5, atol=1e-5)
    assert not np.asarray(out_r)[..., c:].any()


def test_hist_level_fold_recorded():
    """Tracing the level op at otto's training shape (49,502 x 93 rows,
    9 classes + count, depth 6, subtract engine) records 742 kernel steps
    folding into 63 node blocks per feature over one tree's levels."""
    from repro.runtime import tracing as TR
    n, m, c, B = 49502, 93, 10, 256
    S = jax.ShapeDtypeStruct
    with TR.HistFold() as fold:
        for lvl in range(6):
            n_nodes = 2 ** lvl
            prev = (None if lvl == 0
                    else S((m, n_nodes // 2 * B, 128), jnp.float32))
            jax.eval_shape(
                lambda codes, stats, order, counts, prev, lam, md, nn=n_nodes:
                ops.histogram_splits_level(
                    codes, stats, order, counts, prev, lam, md, n_nodes=nn,
                    n_bins=B, subtract=nn > 1, lane_pad=128,
                    interpret=False),
                S((n, m), jnp.uint8), S((n, c), jnp.float32),
                S((n,), jnp.int32), S((n_nodes,), jnp.int32), prev,
                S((), jnp.float32), S((), jnp.float32))
    levels = fold.levels
    assert sorted(k for k in levels) == [
        (m, 99, 2), (m, 101, 4), (m, 105, 8), (m, 113, 16), (m, 129, 32),
        (m, 195, 1)]
    steps = sum(v.steps for v in levels.values())
    blocks = sum(v.blocks for v in levels.values())
    assert (steps // m, blocks // m) == (742, 63)
    # Every level runs the exact float32 path: three bf16 parts of the 10
    # channels in one 128-lane MXU operand per step.
    assert {(v.parts, v.lanes) for v in levels.values()} == {(3, 128)}


def _split_cases():
    rng = np.random.default_rng(5)
    mag = np.logspace(-30, 30, 601)
    wide = np.stack([mag, -mag], axis=1)                   # both signs
    frac = rng.uniform(1.0, 2.0, wide.shape)               # full mantissas
    return {
        "wide": (wide * frac).astype(np.float32),
        "zeros": np.array([[0.0, -0.0], [0.0, 1.0]], np.float32),
        "integers": np.stack([np.arange(-2 ** 12, 2 ** 12),
                              np.arange(2 ** 24 - 2 ** 13, 2 ** 24)],
                             axis=1).astype(np.float32),
        "normal": rng.normal(size=(512, 7)).astype(np.float32),
    }


@pytest.mark.parametrize("case", ["wide", "zeros", "integers", "normal"])
def test_stat_parts_split_is_exact(case):
    """Three bf16 parts add back to the float32 statistic bit for bit,
    over magnitudes 1e-30 to 1e30 of both signs, zeros and integer
    weights; one part is the statistic rounded to bf16."""
    from repro.kernels.hist_kernel import stat_parts
    x = _split_cases()[case]
    s, c = x.shape
    parts = stat_parts(jnp.asarray(x), n_parts=3, lane_pad=8)
    assert parts.dtype == jnp.bfloat16
    assert parts.shape == (s, -(-3 * c // 8) * 8)
    p = np.asarray(parts.astype(jnp.float32))
    hi, mid, lo = p[:, :c], p[:, c:2 * c], p[:, 2 * c:3 * c]
    assert not p[:, 3 * c:].any()
    np.testing.assert_array_equal(
        hi.astype(np.float64) + mid + lo, x.astype(np.float64))
    np.testing.assert_array_equal((hi + mid) + lo, x)
    one = stat_parts(jnp.asarray(x), n_parts=1, lane_pad=8)
    np.testing.assert_array_equal(
        np.asarray(one[:, :c]), np.asarray(jnp.asarray(x, jnp.bfloat16)))


@pytest.mark.parametrize("seed,tn,tile_node", [
    (0, 64, [0, 0, 1, 1, 1]),
    (1, 32, [0, 1, 1, 2, 3, 3, 3, 3]),
    (2, 128, [0, 0, 0]),
])
def test_hist_tiles_kernel_matches_float64(seed, tn, tile_node):
    """The one bf16 pass over three parts is float32-accurate: the
    interpret-mode kernel agrees with a float64 per-node sum to a few
    float32 ulps of the largest |sum| (one bf16 pass is ~2^-9 off), with
    the count channel exact."""
    from repro.kernels.hist_kernel import hist_tiles_pallas, stat_parts
    rng = np.random.default_rng(seed)
    m, B, c = 3, 16, 5
    tiles = len(tile_node)
    s = tn * tiles
    codes = rng.integers(0, B, (m, s))
    grads = (rng.normal(size=(s, c - 1))
             * 10.0 ** rng.uniform(-3, 3, (s, c - 1)))
    weights = rng.integers(0, 4, (s, 1)).astype(np.float64)
    stats = np.concatenate([grads * weights, weights], axis=1).astype(
        np.float32)
    n_nodes = tile_node[-1] + 1
    out = hist_tiles_pallas(
        jnp.asarray(tile_node, jnp.int32), jnp.asarray(codes, jnp.int32),
        stat_parts(jnp.asarray(stats), n_parts=3, lane_pad=8),
        n_nodes=n_nodes, n_bins=B, n_channels=c, n_parts=3, lane_pad=8,
        row_tile=tn, interpret=True)
    node = np.repeat(tile_node, tn)
    want = np.zeros((m, n_nodes, B, c))
    for f in range(m):
        np.add.at(want[f], (node, codes[f]), stats.astype(np.float64))
    got = np.asarray(out, np.float64)
    assert not got[..., c:].any()
    scale = np.abs(want).max()
    assert np.abs(got[..., :c] - want).max() <= 4 * 2.0 ** -23 * scale
    np.testing.assert_array_equal(got[..., c - 1], want[..., c - 1])


@pytest.mark.parametrize("c,hist_dtype,parts,lanes,out_lanes", [
    (6, "float32", 3, 128, 128),          # dionis: k = 5 + count
    (10, "float32", 3, 128, 128),         # otto: 9 classes + count
    (43, "float32", 3, 256, 128),         # 3c just past one lane tile
    (356, "float32", 3, 1152, 384),       # 355 classes, no sketch
    (10, "bfloat16", 1, 128, 128),        # one rounded part
])
def test_hist_parts_lane_rule(c, hist_dtype, parts, lanes, out_lanes):
    """Part count follows ``hist_dtype`` and the operand width follows
    3c: ``round_up(parts * c, 128)`` lanes in one pass, never more lane
    tiles than half of HIGHEST's six passes over ``round_up(c, 128)``;
    the traced kernel records both and writes ``round_up(c, 128)``."""
    from repro.kernels.hist_kernel import (hist_parts, hist_tiles_pallas,
                                           parts_lanes, stat_parts)
    from repro.runtime import tracing as TR
    assert hist_parts(hist_dtype) == parts
    assert parts_lanes(c, parts, 128) == lanes
    assert lanes // 128 <= 3 * out_lanes // 128
    S = jax.ShapeDtypeStruct
    op = jax.eval_shape(lambda x: stat_parts(x, n_parts=parts, lane_pad=128),
                        S((512, c), jnp.float32))
    assert (op.shape, op.dtype) == ((512, lanes), jnp.bfloat16)
    with TR.HistFold() as fold:
        out = jax.eval_shape(
            lambda tn, codes, p: hist_tiles_pallas(
                tn, codes, p, n_nodes=2, n_bins=256, n_channels=c,
                n_parts=parts, lane_pad=128, interpret=False),
            S((2,), jnp.int32), S((7, 512), jnp.int32), op)
    assert (out.shape, out.dtype) == ((7, 2, 256, out_lanes), jnp.float32)
    assert fold.levels == {(7, 2, 2): (14, 14, parts, lanes)}


@pytest.mark.parametrize("subtract", [False, True])
def test_fused_level_op_matches_direct(subtract):
    """ops.histogram_splits_level == direct histograms + split argmax."""
    n, m, B, depth = 520, 7, 16, 3
    codes, stats, _, _ = _rand_problem(7, n=n, m=m, B=B, depth=depth)
    lam, min_data = jnp.float32(1.0), jnp.float32(1.0)
    prev = None
    for lvl, (state, node_pos) in enumerate(
            _routed_state(codes, stats, depth, B)):
        n_nodes = 2 ** lvl
        gain_k, idx_k, hist_native = ops.histogram_splits_level(
            codes, stats, state.order, state.counts, prev, lam, min_data,
            n_nodes=n_nodes, n_bins=B, subtract=subtract and lvl > 0,
            row_tile=64, interpret=True)
        prev = hist_native
        direct = H.build_histograms_jnp(codes, node_pos, stats,
                                        n_nodes=n_nodes, n_bins=B)
        c = stats.shape[1]
        hist4 = hist_native.reshape(m, n_nodes, B, -1)[..., :c].transpose(
            1, 0, 2, 3)
        tol = dict(rtol=1e-4, atol=1e-3) if subtract else dict(rtol=1e-5,
                                                               atol=1e-4)
        np.testing.assert_allclose(np.asarray(hist4), np.asarray(direct),
                                   **tol)
        hist_mnb = direct.transpose(1, 0, 2, 3).reshape(m, n_nodes * B, c)
        g_ref, i_ref = ref.split_scan_ref(hist_mnb, lam, min_data,
                                          jnp.ones((m,), jnp.float32),
                                          n_nodes=n_nodes, n_bins=B)
        np.testing.assert_array_equal(np.asarray(idx_k), np.asarray(i_ref))
        np.testing.assert_allclose(np.asarray(gain_k), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-4)


def test_hist_tiles_kernel_bf16_fp32_accumulation():
    """Satellite: hist_dtype='bfloat16' rounds the MXU *inputs* only —
    accumulation stays fp32, so gradient channels match the fp32 oracle to
    bf16 input rounding (~2^-8 relative) and the count channel (small
    integer weights, exact in bf16) stays integer-exact."""
    from repro.kernels.hist_kernel import (hist_parts, hist_tiles_pallas,
                                           stat_parts)
    ks = jax.random.split(jax.random.key(0), 2)
    m, tn, tiles, B, c = 4, 64, 3, 16, 4
    codes_t = jax.random.randint(ks[0], (m, tn * tiles), 0, B, jnp.int32)
    grads = jax.random.normal(ks[1], (tn * tiles, c - 1), jnp.float32)
    stats = jnp.concatenate([grads, jnp.ones((tn * tiles, 1))], axis=1)
    tile_node = jnp.asarray([0, 0, 1], jnp.int32)
    kw = dict(n_nodes=2, n_bins=B, n_channels=c, lane_pad=1, row_tile=tn)
    n_bf, n_fp = hist_parts("bfloat16"), hist_parts("float32")
    assert (n_bf, n_fp) == (1, 3)
    out_bf = hist_tiles_pallas(
        tile_node, codes_t, stat_parts(stats, n_parts=n_bf, lane_pad=1),
        **kw, n_parts=n_bf, interpret=True)
    out_fp = ref.histogram_tiles_ref(
        tile_node, codes_t, stat_parts(stats, n_parts=n_fp, lane_pad=1),
        **kw, n_parts=n_fp)
    assert out_bf.shape == (m, 2, B, c)
    assert out_bf.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(out_fp)))
    drift = float(jnp.max(jnp.abs(out_bf - out_fp)))
    assert drift <= 1e-2 * scale, (drift, scale)
    # Count channel: sums of exact bf16 ones are exact in fp32 accumulation.
    np.testing.assert_array_equal(np.asarray(out_bf[..., -1]),
                                  np.asarray(out_fp[..., -1]))
    with pytest.raises(ValueError):
        hist_parts("float16")


def test_subtraction_drift_bounded_bf16():
    """Satellite: the sibling-subtraction drift assertion, mirrored at
    bf16 — ``parent − built`` cancellation on bf16-rounded inputs stays
    within the documented ~2^-8-relative envelope (vs 1e-3 absolute at
    fp32; see docs/performance.md)."""
    n, m, B, depth = 520, 6, 16, 4
    codes, stats, _, _ = _rand_problem(21, n=n, m=m, B=B, depth=depth)
    prev = None
    for lvl, (state, node_pos) in enumerate(
            _routed_state(codes, stats, depth, B)):
        n_nodes = 2 ** lvl
        _, _, prev = ops.histogram_splits_level(
            codes, stats, state.order, state.counts, prev,
            jnp.float32(1.0), jnp.float32(1.0), n_nodes=n_nodes, n_bins=B,
            subtract=lvl > 0, row_tile=64, hist_dtype="bfloat16",
            interpret=True)
        direct = H.build_histograms_jnp(codes, node_pos, stats,
                                        n_nodes=n_nodes, n_bins=B)
        c = stats.shape[1]
        hist4 = prev.reshape(m, n_nodes, B, -1)[..., :c].transpose(
            1, 0, 2, 3)
        scale = max(float(jnp.max(jnp.abs(direct))), 1.0)
        drift = float(jnp.max(jnp.abs(hist4 - direct)))
        # bf16 inputs round at 2^-8 relative; the subtraction chain can at
        # most double it per level.
        assert drift <= 4e-2 * scale, (lvl, drift, scale)


def test_grow_tree_bf16_close_to_fp32():
    """End-to-end: a bf16-stats tree picks identical splits on this fixed
    seed (near-ties closer than the bf16 rounding envelope may legally flip
    on other seeds — same caveat as the fp32 subtraction bound) and then
    bit-identical leaf values (the leaf pass always runs fp32 on the full
    gradients)."""
    codes, stats, G, Hd = _rand_problem(30, n=450, m=8, B=16, depth=4)
    kw = dict(depth=4, n_bins=16, lam=1.0, use_kernel="interpret")
    t32, _ = T.grow_tree(codes, stats, G, Hd, hist_engine="subtract", **kw)
    t16, _ = T.grow_tree(codes, stats, G, Hd, hist_engine="subtract",
                         hist_dtype="bfloat16", **kw)
    np.testing.assert_array_equal(np.asarray(t32.feat), np.asarray(t16.feat))
    np.testing.assert_array_equal(np.asarray(t32.thr), np.asarray(t16.thr))
    np.testing.assert_allclose(np.asarray(t32.value), np.asarray(t16.value),
                               rtol=1e-4, atol=1e-5)


def test_leafwise_bf16_smoke():
    """bf16 stats channel rides the leaf-wise per-node builder too."""
    codes, stats, G, Hd = _rand_problem(23, n=300, m=6, B=16, depth=3)
    kw = dict(depth=3, max_leaves=8, n_bins=16, lam=1.0,
              use_kernel="interpret")
    t32, p32 = T.grow_tree_leafwise(codes, stats, G, Hd, **kw)
    t16, p16 = T.grow_tree_leafwise(codes, stats, G, Hd,
                                    hist_dtype="bfloat16", **kw)
    np.testing.assert_array_equal(np.asarray(p32), np.asarray(p16))
    np.testing.assert_allclose(np.asarray(t32.value), np.asarray(t16.value),
                               rtol=1e-4, atol=1e-5)


def test_fused_level_op_lane_padding_zero():
    """Lane-padding channels of the carried native hist stay exactly zero
    through subtraction (parent − built cannot leak into padding)."""
    n, m, B = 256, 3, 8
    codes, stats, _, _ = _rand_problem(9, n=n, m=m, B=B, depth=3)
    c = stats.shape[1]
    prev = None
    for lvl, (state, _) in enumerate(_routed_state(codes, stats, 3, B)):
        _, _, prev = ops.histogram_splits_level(
            codes, stats, state.order, state.counts, prev,
            jnp.float32(1.0), jnp.float32(1.0), n_nodes=2 ** lvl, n_bins=B,
            subtract=lvl > 0, row_tile=64, interpret=True)
        assert np.all(np.asarray(prev)[..., c:] == 0.0)


# ---------------------------------------------------------------------------
# grow_tree engine equivalence (all kernel modes, weights, feature masks)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["jnp", "interpret"])
@pytest.mark.parametrize("engine", ["partition", "subtract"])
def test_grow_tree_engines_match_direct(mode, engine):
    codes, stats, G, Hd = _rand_problem(11, n=450, m=8, B=16, depth=4)
    kw = dict(depth=4, n_bins=16, lam=1.0, use_kernel=mode)
    t0, p0 = T.grow_tree(codes, stats, G, Hd, hist_engine="direct", **kw)
    t1, p1 = T.grow_tree(codes, stats, G, Hd, hist_engine=engine, **kw)
    np.testing.assert_array_equal(np.asarray(t0.feat), np.asarray(t1.feat))
    np.testing.assert_array_equal(np.asarray(t0.thr), np.asarray(t1.thr))
    np.testing.assert_allclose(np.asarray(t0.value), np.asarray(t1.value),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(p0), np.asarray(p1))


def test_grow_tree_engine_with_goss_weights_and_mask():
    """Non-unit count-channel weights (GOSS-style) + colsample mask."""
    rng = np.random.default_rng(5)
    n = 400
    w = np.where(rng.random(n) < 0.3, 2.5, np.where(rng.random(n) < 0.5,
                                                    1.0, 0.0))
    codes, stats, G, Hd = _rand_problem(5, n=n, m=8, B=16, depth=4,
                                        weights=w.astype(np.float32))
    fmask = jnp.asarray(rng.random(8) < 0.75)
    kw = dict(depth=4, n_bins=16, lam=1.0, feature_mask=fmask,
              use_kernel="jnp")
    t0, _ = T.grow_tree(codes, stats, G, Hd, hist_engine="direct", **kw)
    t1, _ = T.grow_tree(codes, stats, G, Hd, hist_engine="subtract", **kw)
    np.testing.assert_array_equal(np.asarray(t0.feat), np.asarray(t1.feat))
    np.testing.assert_array_equal(np.asarray(t0.thr), np.asarray(t1.thr))
    np.testing.assert_allclose(np.asarray(t0.value), np.asarray(t1.value),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# End-to-end fits: engine equivalence across the 5 sketch methods + modes
# ---------------------------------------------------------------------------

def _plain_data(seed, n=500, m=8, d=5):
    """Random data WITHOUT the tabular generator's redundant
    linear-combination features: those produce split gains tied closer than
    the documented subtraction drift, where either tie-break is legal.
    Plain noise has no knife-edge ties, so exact structure equality is a
    meaningful fixed-seed contract."""
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, m)).astype(np.float32),
            rng.integers(0, d, n).astype(np.int32))


@pytest.mark.parametrize("method", ["none", "top_outputs", "random_sampling",
                                    "random_projection", "truncated_svd"])
def test_fit_engines_identical_all_sketch_methods(method):
    """Fixed-seed fits: identical split structure and near-identical loss
    between the new default engine and the direct builder, for every sketch
    method (jnp mode — what CPU CI executes end to end)."""
    X, y = _plain_data(13)
    kw = dict(loss="multiclass", n_trees=5, depth=4, learning_rate=0.3,
              n_bins=32, sketch_method=method, sketch_k=2, use_kernel="jnp")
    m_dir = SketchBoost(GBDTConfig(hist_engine="direct", **kw)).fit(X, y)
    m_sub = SketchBoost(GBDTConfig(hist_engine="subtract", **kw)).fit(X, y)
    np.testing.assert_array_equal(np.asarray(m_dir.forest.feat),
                                  np.asarray(m_sub.forest.feat))
    np.testing.assert_array_equal(np.asarray(m_dir.forest.thr),
                                  np.asarray(m_sub.forest.thr))
    np.testing.assert_allclose(np.asarray(m_dir.forest.value),
                               np.asarray(m_sub.forest.value),
                               rtol=1e-4, atol=1e-5)
    assert m_sub.eval_loss(X, y) == pytest.approx(m_dir.eval_loss(X, y),
                                                  rel=1e-4)


def test_fit_sgb_goss_engine_parity():
    X, y = _plain_data(14, d=4)
    for kw_extra in (dict(subsample=0.7), dict(goss_a=0.2, goss_b=0.3)):
        kw = dict(loss="multiclass", n_trees=4, depth=4, learning_rate=0.3,
                  n_bins=32, use_kernel="jnp", **kw_extra)
        m_dir = SketchBoost(GBDTConfig(hist_engine="direct", **kw)).fit(X, y)
        m_sub = SketchBoost(GBDTConfig(hist_engine="subtract",
                                       **kw)).fit(X, y)
        np.testing.assert_array_equal(np.asarray(m_dir.forest.feat),
                                      np.asarray(m_sub.forest.feat))
        np.testing.assert_allclose(np.asarray(m_dir.forest.value),
                                   np.asarray(m_sub.forest.value),
                                   rtol=1e-4, atol=1e-5)


def test_one_vs_all_routed_through_new_engine():
    """The vmapped one_vs_all grower runs the partitioned engine (the
    per-output growers carry independent partitions under vmap)."""
    X, y = make_tabular("multiclass", 450, 8, 4, seed=15)
    kw = dict(loss="multiclass", strategy="one_vs_all", n_trees=4, depth=3,
              learning_rate=0.3, n_bins=32, use_kernel="jnp")
    m_dir = SketchBoost(GBDTConfig(hist_engine="direct", **kw)).fit(X, y)
    m_sub = SketchBoost(GBDTConfig(hist_engine="subtract", **kw)).fit(X, y)
    np.testing.assert_array_equal(np.asarray(m_dir.forest.feat),
                                  np.asarray(m_sub.forest.feat))
    np.testing.assert_array_equal(np.asarray(m_dir.forest.thr),
                                  np.asarray(m_sub.forest.thr))
    np.testing.assert_allclose(np.asarray(m_dir.predict(X)),
                               np.asarray(m_sub.predict(X)),
                               rtol=1e-4, atol=1e-5)


def test_interpret_e2e_new_engine(monkeypatch):
    """REPRO_PALLAS_INTERPRET=1 + use_kernel=True: the full fit runs the
    partitioned tiles + split-scan Pallas kernels under the interpreter.
    Compared against the jnp path on loss (split near-ties closer than the
    documented subtraction drift may legally tie-break differently across
    modes, so per-element prediction equality is not the contract here)."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert H.resolve_kernel_mode(True) == "interpret"
    X, y = make_tabular("multiclass", 250, 6, 3, seed=16)
    kw = dict(loss="multiclass", n_trees=3, depth=3, learning_rate=0.3,
              n_bins=32, sketch_method="top_outputs", sketch_k=2)
    m_ker = SketchBoost(GBDTConfig(use_kernel=True, **kw)).fit(X, y)
    assert m_ker.cfg.use_kernel == "interpret"
    assert m_ker.cfg.hist_engine == "subtract"
    m_jnp = SketchBoost(GBDTConfig(use_kernel="jnp", **kw)).fit(X, y)
    assert m_ker.eval_loss(X, y) == pytest.approx(m_jnp.eval_loss(X, y),
                                                  rel=5e-2)
    p = np.asarray(m_ker.predict(X))
    assert np.all(np.isfinite(p))
    np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-4)


def test_one_vs_all_interpret_kernel_smoke():
    """vmap over the partitioned Pallas kernel pipeline (interpret mode)."""
    X, y = make_tabular("multiclass", 200, 6, 3, seed=17)
    cfg = GBDTConfig(loss="multiclass", strategy="one_vs_all", n_trees=2,
                     depth=3, n_bins=16, learning_rate=0.3,
                     use_kernel="interpret")
    m = SketchBoost(cfg).fit(X, y)
    assert np.isfinite(m.eval_loss(X, y))


def test_scan_python_loop_parity_under_new_engine():
    """Same engine => bit-identical forests between the two loop modes."""
    X, y = make_tabular("multiclass", 400, 8, 4, seed=18)
    kw = dict(loss="multiclass", n_trees=6, depth=4, learning_rate=0.3,
              scan_chunk=4, use_kernel="jnp", hist_engine="subtract")
    m_scan = SketchBoost(GBDTConfig(loop="scan", **kw)).fit(X, y)
    m_py = SketchBoost(GBDTConfig(loop="python", **kw)).fit(X, y)
    np.testing.assert_array_equal(np.asarray(m_scan.forest.feat),
                                  np.asarray(m_py.forest.feat))
    np.testing.assert_allclose(np.asarray(m_scan.forest.value),
                               np.asarray(m_py.forest.value),
                               rtol=1e-5, atol=1e-6)


def test_hist_engine_resolution():
    assert H.resolve_hist_engine("auto") == "subtract"
    assert H.resolve_hist_engine(None) == "subtract"
    for e in H.HIST_ENGINES:
        assert H.resolve_hist_engine(e) == e
    with pytest.raises(ValueError):
        H.resolve_hist_engine("sorted")
    cfg = GBDTConfig().resolve(4)
    assert cfg.hist_engine == "subtract"


def test_resolve_dispatch_shared_helper():
    """The one resolver every dispatch site uses (histogram, fused splits,
    forest traversal, TreeSHAP): mode string + interpret flag."""
    assert ops.resolve_dispatch(False) == ("jnp", False)
    assert ops.resolve_dispatch("interpret") == ("interpret", True)
    assert ops.resolve_dispatch("pallas") == ("pallas", False)
    # legacy override: interpret=True forces the interpreter for any kernel
    # request; interpret=False forces the compiled kernel; both are ignored
    # for explicit jnp requests.
    assert ops.resolve_dispatch("pallas", True) == ("interpret", True)
    assert ops.resolve_dispatch("interpret", False) == ("pallas", False)
    assert ops.resolve_dispatch(False, True) == ("jnp", False)
    assert ops.resolve_dispatch("jnp", True) == ("jnp", False)
