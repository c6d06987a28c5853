"""Property-based invariants for the distributed partition/collective layer.

Uses `hypothesis` when installed, else the deterministic shim from
`_hypothesis_fallback.py` (see conftest.py).  Three families:

1. `LevelState` under row sharding: advancing each shard's partition with its
   slice of the same global routing bits is a stable shard-local permutation,
   and the per-shard node counts sum to the global counts — the property the
   distributed grower's per-node count psum relies on to pick the same
   smaller child on every shard.
2. `build_level_built` under row sharding: summing per-shard compacted builds
   (with the globally chosen side and a full-size ``n_build`` buffer) equals
   the single-device build bit-for-bit on integer-valued stats — the psum
   the level-wise grower performs.
3. `sketched_hist_psum` contracts: shape and dtype are preserved, the count
   channel is exact, the compressor passes through (bitwise) when the
   channel count fits the JL width, and the reconstruction depends only on
   the exact psum, not on how the payload was sharded.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

jax = pytest.importorskip("jax")
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import histogram as H
from repro.distributed import compression as C

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs >= 8 (emulated) devices; tests/conftest.py sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")

SHARDS = 4


def _shards(n):
    return [slice(i * (n // SHARDS), (i + 1) * (n // SHARDS))
            for i in range(SHARDS)]


def _advance_many(state, bits_history):
    for bits in bits_history:
        state = H.advance_level_state(state, bits)
    return state


# ---------------------------------------------------------------------------
# 1. LevelState sharding invariants.
# ---------------------------------------------------------------------------

@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 3))
def test_level_state_counts_psum_to_global(seed, depth):
    n = 64
    rng = np.random.default_rng(seed)
    bits_history = [jnp.asarray(rng.integers(0, 2, size=n), jnp.int32)
                    for _ in range(depth + 1)]
    global_state = _advance_many(H.init_level_state(n), bits_history)

    shard_counts = []
    for sl in _shards(n):
        loc = _advance_many(H.init_level_state(n // SHARDS),
                            [b[sl] for b in bits_history])
        shard_counts.append(np.asarray(loc.counts))
        # Stability: within a shard, rows of each node keep dataset order.
        order, nodes = np.asarray(loc.order), np.asarray(loc.node_perm)
        for nd in np.unique(nodes):
            rows = order[nodes == nd]
            assert (np.diff(rows) > 0).all()
    # The psum the distributed grower performs: shard counts sum to the
    # global counts, so every shard picks the same smaller child.
    summed = np.sum(shard_counts, axis=0)
    np.testing.assert_array_equal(summed, np.asarray(global_state.counts))
    side_global, _ = H.smaller_children(global_state.counts)
    side_shard, _ = H.smaller_children(jnp.asarray(summed))
    np.testing.assert_array_equal(np.asarray(side_global),
                                  np.asarray(side_shard))


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000))
def test_advance_is_stable_permutation(seed):
    n = 96
    rng = np.random.default_rng(seed)
    bits_history = [jnp.asarray(rng.integers(0, 2, size=n), jnp.int32)
                    for _ in range(3)]
    adv = _advance_many(H.init_level_state(n), bits_history)
    order = np.asarray(adv.order)
    assert sorted(order.tolist()) == list(range(n))          # permutation
    nodes = np.asarray(adv.node_perm)
    assert (np.diff(nodes) >= 0).all()                        # sorted by node
    counts = np.asarray(adv.counts)
    np.testing.assert_array_equal(
        np.bincount(nodes, minlength=counts.shape[0]), counts)


@settings(max_examples=10, deadline=None)
@given(st.integers(2, 32))
def test_smaller_children_picks_minority(n_pairs):
    rng = np.random.default_rng(n_pairs)
    counts = jnp.asarray(rng.integers(0, 100, size=2 * n_pairs), jnp.int32)
    side, is_built = H.smaller_children(counts)
    c = np.asarray(counts).reshape(-1, 2)
    s = np.asarray(side)
    chosen = c[np.arange(n_pairs), s]
    other = c[np.arange(n_pairs), 1 - s]
    assert (chosen <= other).all()                 # never the larger child
    assert (s[c[:, 0] == c[:, 1]] == 0).all()      # ties break left
    built = np.asarray(is_built)
    np.testing.assert_array_equal(built.reshape(-1, 2).sum(1),
                                  np.ones(n_pairs))


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 8), st.integers(0, 10_000))
def test_interleave_children_roundtrip(n_pairs, seed):
    rng = np.random.default_rng(seed)
    side = jnp.asarray(rng.integers(0, 2, size=n_pairs), jnp.int32)
    built = jnp.asarray(rng.normal(size=(n_pairs, 3)), jnp.float32)
    sib = jnp.asarray(rng.normal(size=(n_pairs, 3)), jnp.float32)
    out = np.asarray(H.interleave_children(side, built, sib))
    for p in range(n_pairs):
        b, s = np.asarray(built[p]), np.asarray(sib[p])
        want_left, want_right = (b, s) if int(side[p]) == 0 else (s, b)
        np.testing.assert_array_equal(out[2 * p], want_left)
        np.testing.assert_array_equal(out[2 * p + 1], want_right)


# ---------------------------------------------------------------------------
# 2. Sharded compacted build == single-device build (the grower's psum).
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 2))
def test_sharded_build_level_built_sums_to_global(seed, depth):
    n, m, B, c = 64, 3, 8, 4
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(rng.integers(0, B, size=(n, m)), jnp.uint8)
    # Integer stats: fp32 sums are exact, so shard-sum == global bitwise.
    stats = jnp.asarray(rng.integers(-4, 5, size=(n, c)), jnp.float32)
    bits_history = [jnp.asarray(rng.integers(0, 2, size=n), jnp.int32)
                    for _ in range(depth)]
    state = _advance_many(H.init_level_state(n), bits_history)
    n_nodes = 2 ** depth
    side, _ = H.smaller_children(state.counts)

    full = H.build_level_built(codes, stats, state, side,
                               n_nodes=n_nodes, n_bins=B, n_build=n)

    acc = np.zeros_like(np.asarray(full))
    for sl in _shards(n):
        loc = _advance_many(H.init_level_state(n // SHARDS),
                            [b[sl] for b in bits_history])
        # Full-size local buffer: the globally-smaller child can be locally
        # large (the silent-truncation regression this suite pins down).
        part = H.build_level_built(codes[sl], stats[sl], loc, side,
                                   n_nodes=n_nodes, n_bins=B,
                                   n_build=n // SHARDS)
        acc += np.asarray(part)
    np.testing.assert_array_equal(acc, np.asarray(full))


# ---------------------------------------------------------------------------
# 3. Collective compression contracts.
# ---------------------------------------------------------------------------

def _run_hist_psum(hist_global, k):
    """Run sketched_hist_psum inside shard_map over a 4-way row axis.

    ``hist_global`` has a leading (SHARDS,) axis holding each shard's local
    payload; returns shard 0's reduced copy (all shards agree — the output
    is replicated over the row axis by construction).
    """
    mesh = Mesh(np.asarray(jax.devices()[:SHARDS]), ("rows",))
    key = jax.random.key(0)

    def body(h_l, k_arr):
        return C.sketched_hist_psum(h_l[0], k_arr, ("rows",), k)[None]

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("rows"), P()),
                           out_specs=P("rows")))
    out = np.asarray(fn(hist_global, key))
    np.testing.assert_array_equal(out, np.broadcast_to(out[:1], out.shape))
    return out[0]


@settings(max_examples=6, deadline=None)
@given(st.integers(2, 6), st.integers(0, 10_000))
def test_sketched_hist_psum_count_channel_exact(c, seed):
    rng = np.random.default_rng(seed)
    hist = jnp.asarray(rng.normal(size=(SHARDS, 5, c + 1)), jnp.float32)
    hist = hist.at[..., -1].set(
        jnp.asarray(rng.integers(0, 9, size=(SHARDS, 5)), jnp.float32))
    k = max(1, c - 1)                       # strictly lossy width
    out = _run_hist_psum(hist, k)
    assert out.shape == hist.shape[1:] and out.dtype == np.float32
    np.testing.assert_array_equal(out[..., -1],
                                  np.asarray(hist[..., -1]).sum(0))


@settings(max_examples=6, deadline=None)
@given(st.integers(1, 4), st.integers(0, 10_000))
def test_sketched_hist_psum_passthrough_when_wide(c, seed):
    rng = np.random.default_rng(seed)
    hist = jnp.asarray(rng.integers(-3, 4, size=(SHARDS, 6, c + 1)),
                       jnp.float32)
    out = _run_hist_psum(hist, c)           # k >= channels -> identity
    np.testing.assert_array_equal(out, np.asarray(hist).sum(0))


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 10_000))
def test_sketched_hist_psum_is_projection_of_exact_psum(seed):
    # Linearity: reconstruction == orthogonal projection of the EXACT psum,
    # so it is invariant to how the payload was sharded.
    rng = np.random.default_rng(seed)
    c, k = 8, 4
    a = jnp.asarray(rng.normal(size=(SHARDS, 6, c + 1)), jnp.float32)
    b = np.zeros((SHARDS, 6, c + 1), np.float32)
    b[0] = np.asarray(a).sum(0)             # all mass on one shard
    out_a = _run_hist_psum(a, k)
    out_b = _run_hist_psum(jnp.asarray(b), k)
    np.testing.assert_allclose(out_a, out_b, rtol=1e-5, atol=1e-5)
