"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# Split-scan kernel
# ---------------------------------------------------------------------------

def _random_hist_problem(seed, n, m, B, nodes, k):
    """Random binned data -> (m, nodes*B, c) histograms + core split answer."""
    from repro.core import histogram as H
    from repro.core import split as S
    ks = jax.random.split(jax.random.key(seed), 3)
    codes = jax.random.randint(ks[0], (n, m), 0, B, jnp.int32)
    node = jax.random.randint(ks[1], (n,), 0, nodes, jnp.int32)
    stats = jnp.concatenate(
        [jax.random.normal(ks[2], (n, k), jnp.float32),
         jnp.ones((n, 1), jnp.float32)], axis=1)
    hist4 = H.build_histograms_jnp(codes, node, stats, n_nodes=nodes, n_bins=B)
    hist_mnb = hist4.transpose(1, 0, 2, 3).reshape(m, nodes * B, k + 1)
    return codes, node, stats, hist4, hist_mnb, S


@pytest.mark.parametrize("n,m,B,nodes,k", [
    (128, 3, 8, 1, 2),       # root node
    (512, 11, 16, 4, 3),     # feature count off the m_tile grid (padding path)
    (300, 8, 32, 8, 5),
    (256, 4, 256, 2, 1),     # full 256-bin scan
])
def test_split_scan_kernel_matches_ref(n, m, B, nodes, k):
    _, _, _, _, hist_mnb, _ = _random_hist_problem(n + m, n, m, B, nodes, k)
    lam, min_data = jnp.float32(1.0), jnp.float32(2.0)
    mask = jnp.ones((m,), jnp.float32)
    g_ref, i_ref = ref.split_scan_ref(hist_mnb, lam, min_data, mask,
                                      n_nodes=nodes, n_bins=B)
    g_ker, i_ker = ops.split_scan(hist_mnb, lam, min_data, n_nodes=nodes,
                                  n_bins=B, interpret=True)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(i_ker), np.asarray(i_ref))


def test_split_scan_ref_matches_core_split():
    """The kernel oracle and core/split.py agree on gain AND arg-max."""
    _, _, _, hist4, hist_mnb, S = _random_hist_problem(0, 400, 9, 16, 4, 3)
    lam, min_data = jnp.float32(1.0), jnp.float32(1.0)
    gain = S.split_scores(hist4, lam, min_data)
    flat = gain.reshape(4, 9 * 16)
    g_ref, i_ref = ref.split_scan_ref(hist_mnb, lam, min_data,
                                      jnp.ones((9,), jnp.float32),
                                      n_nodes=4, n_bins=16)
    np.testing.assert_allclose(np.asarray(g_ref), np.asarray(jnp.max(flat, 1)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(i_ref),
                                  np.asarray(jnp.argmax(flat, 1)))


def test_split_scan_kernel_feature_mask():
    _, _, _, _, hist_mnb, _ = _random_hist_problem(3, 300, 10, 16, 2, 2)
    lam, min_data = jnp.float32(1.0), jnp.float32(1.0)
    mask = (jnp.arange(10) % 3 != 0).astype(jnp.float32)
    g_ref, i_ref = ref.split_scan_ref(hist_mnb, lam, min_data, mask,
                                      n_nodes=2, n_bins=16)
    g_ker, i_ker = ops.split_scan(hist_mnb, lam, min_data, mask, n_nodes=2,
                                  n_bins=16, interpret=True)
    np.testing.assert_allclose(np.asarray(g_ker), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(i_ker), np.asarray(i_ref))
    # masked features never win
    assert not np.any(np.isin(np.asarray(i_ker) // 16, [0, 3, 6, 9]))


def test_split_scan_kernel_no_legal_split():
    """min_data too high -> every node reports -inf / idx 0 (leaf demotion)."""
    _, _, _, _, hist_mnb, _ = _random_hist_problem(4, 64, 4, 8, 2, 2)
    g_ker, i_ker = ops.split_scan(hist_mnb, jnp.float32(1.0),
                                  jnp.float32(1e9), n_nodes=2, n_bins=8,
                                  interpret=True)
    assert np.all(np.asarray(g_ker) == -np.inf)
    assert np.all(np.asarray(i_ker) == 0)


def test_grow_tree_kernel_mode_matches_jnp():
    from repro.core import tree as T
    rng = np.random.default_rng(2)
    n, m, d, depth = 256, 7, 3, 4
    codes = jnp.asarray(rng.integers(0, 16, (n, m)).astype(np.uint8))
    G = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    Hd = jnp.ones((n, d), jnp.float32)
    stats = jnp.concatenate([G, jnp.ones((n, 1), jnp.float32)], 1)
    t1, p1 = T.grow_tree(codes, stats, G, Hd, depth=depth, n_bins=16,
                         lam=1.0, use_kernel="jnp")
    t2, p2 = T.grow_tree(codes, stats, G, Hd, depth=depth, n_bins=16,
                         lam=1.0, use_kernel="interpret")
    np.testing.assert_array_equal(np.asarray(t1.feat), np.asarray(t2.feat))
    np.testing.assert_array_equal(np.asarray(t1.thr), np.asarray(t2.thr))
    np.testing.assert_allclose(np.asarray(t1.value), np.asarray(t2.value),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


# ---------------------------------------------------------------------------
# Flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,dh,causal,window", [
    (1, 2, 2, 64, 32, True, None),
    (2, 4, 2, 128, 32, True, None),       # GQA 2:1
    (1, 8, 1, 96, 64, True, None),        # MQA, ragged seq
    (2, 4, 4, 128, 32, False, None),      # bidirectional
    (1, 4, 2, 256, 32, True, 64),         # sliding window
])
def test_flash_attention_matches_ref(b, hq, hkv, s, dh, causal, window):
    ks = jax.random.split(jax.random.key(s + hq), 3)
    q = jax.random.normal(ks[0], (b, hq, s, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, dh), jnp.float32)
    o_ref = ref.mha_ref(q, k, v, causal=causal, window=window)
    o_ker = ops.flash_attention(q, k, v, causal=causal, window=window,
                                block_q=64, block_k=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_attention_bf16():
    ks = jax.random.split(jax.random.key(5), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 32), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 2, 128, 32), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 2, 128, 32), jnp.bfloat16)
    o_ref = ref.mha_ref(q, k, v, causal=True)
    o_ker = ops.flash_attention(q, k, v, causal=True, interpret=True)
    np.testing.assert_allclose(
        np.asarray(o_ker, np.float32), np.asarray(o_ref, np.float32),
        rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# Decode attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,dh,window", [
    (2, 4, 2, 128, 32, None),
    (1, 8, 8, 512, 64, None),
    (3, 4, 1, 200, 32, None),            # MQA + ragged cache
    (2, 4, 2, 256, 32, 64),              # sliding window
])
def test_decode_attention_matches_ref(b, hq, hkv, s, dh, window):
    ks = jax.random.split(jax.random.key(b * s), 4)
    q = jax.random.normal(ks[0], (b, hq, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, s, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, s, dh), jnp.float32)
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1, jnp.int32)
    o_ref = ref.decode_attention_ref(q, k, v, lengths, window=window)
    o_ker = ops.decode_attention(q, k, v, lengths, window=window,
                                 block_s=128, interpret=True)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Model-layer attention path consistency (jnp chunked vs kernels)
# ---------------------------------------------------------------------------

def test_chunked_attention_matches_kernel_semantics():
    from repro.models.layers import chunked_attention
    ks = jax.random.split(jax.random.key(9), 3)
    b, s, hq, hkv, dh = 2, 96, 4, 2, 32
    q = jax.random.normal(ks[0], (b, s, hq, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, dh), jnp.float32)
    out = chunked_attention(q, k, v, causal=True, chunk=32)
    o_ref = ref.mha_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(o_ref.transpose(0, 2, 1, 3)),
                               rtol=2e-3, atol=2e-3)


def test_chunked_attention_window_matches_ref():
    from repro.models.layers import chunked_attention
    ks = jax.random.split(jax.random.key(11), 3)
    b, s, h, dh, w = 1, 128, 2, 32, 48
    q = jax.random.normal(ks[0], (b, s, h, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, dh), jnp.float32)
    out = chunked_attention(q, k, v, causal=True, window=w, chunk=32)
    o_ref = ref.mha_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                        v.transpose(0, 2, 1, 3), causal=True, window=w)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(o_ref.transpose(0, 2, 1, 3)),
                               rtol=2e-3, atol=2e-3)
