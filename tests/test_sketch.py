"""Sketch operators: paper Section 3 + Appendix A properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import sketch as SK

jax.config.update("jax_enable_x64", False)


def score(G, vR, lam=1.0):
    """S_G(R) = ||G^T v_R||^2 / (|R| + lam)  (paper eq. before Sec 3.1)."""
    G = np.asarray(G, np.float64)
    num = np.sum((G.T @ vR) ** 2)
    return num / (vR.sum() + lam)


def rand_G(rng, n, d, spiky=False):
    G = rng.normal(size=(n, d)).astype(np.float32)
    if spiky:                      # a few dominant output columns
        G[:, : max(d // 8, 1)] *= 10.0
    return G


# ---------------------------------------------------------------------------
# Construction correctness
# ---------------------------------------------------------------------------

def test_none_is_identity(rng):
    G = rand_G(rng, 64, 12)
    Gk = SK.build_sketch(jnp.asarray(G), method="none", k=5)
    np.testing.assert_allclose(np.asarray(Gk), G, rtol=1e-6)


def test_k_ge_d_is_identity(rng):
    G = rand_G(rng, 32, 6)
    Gk = SK.build_sketch(jnp.asarray(G), method="top_outputs", k=6)
    np.testing.assert_allclose(np.asarray(Gk), G, rtol=1e-6)


def test_top_outputs_selects_largest_columns(rng):
    G = rand_G(rng, 128, 16, spiky=True)
    k = 3
    Gk = np.asarray(SK.build_sketch(jnp.asarray(G), method="top_outputs", k=k))
    norms = np.sum(G ** 2, axis=0)
    top = np.argsort(norms)[::-1][:k]
    got = {tuple(np.round(Gk[:, j], 4)) for j in range(k)}
    want = {tuple(np.round(G[:, j], 4)) for j in top}
    assert got == want


def test_random_sampling_is_unbiased(rng):
    """E[G_k G_k^T] = G G^T over sampling draws (Sec 3.2 scaling)."""
    G = rand_G(rng, 24, 8, spiky=True)
    target = G @ G.T
    acc = np.zeros_like(target)
    trials = 400
    for t in range(trials):
        Gk = np.asarray(SK.build_sketch(jnp.asarray(G),
                                        method="random_sampling", k=4,
                                        key=jax.random.key(t)))
        acc += Gk @ Gk.T
    est = acc / trials
    # Unbiased up to Monte-Carlo noise; compare on the dominant scale.
    err = np.abs(est - target).max() / np.abs(target).max()
    assert err < 0.25, err


def test_random_projection_shape_and_variance(rng):
    G = rand_G(rng, 64, 32)
    Gk = np.asarray(SK.build_sketch(jnp.asarray(G),
                                    method="random_projection", k=8,
                                    key=jax.random.key(0)))
    assert Gk.shape == (64, 8)
    # E||Gk row||^2 = ||G row||^2 (JL isometry in expectation)
    r_in = np.sum(G ** 2, axis=1)
    r_out = np.sum(Gk ** 2, axis=1)
    assert 0.5 < np.median(r_out / r_in) < 2.0


def test_truncated_svd_matches_numpy(rng):
    G = rand_G(rng, 48, 10)
    k = 3
    Gk = np.asarray(SK.build_sketch(jnp.asarray(G), method="truncated_svd",
                                    k=k))
    U, s, Vt = np.linalg.svd(G, full_matrices=False)
    ref = U[:, :k] * s[:k]
    # Equal up to column sign/order: compare Gram matrices.
    np.testing.assert_allclose(Gk @ Gk.T, ref @ ref.T, atol=1e-2)


def test_missing_key_raises(rng):
    G = jnp.asarray(rand_G(rng, 16, 8))
    with pytest.raises(ValueError):
        SK.build_sketch(G, method="random_projection", k=2)
    with pytest.raises(ValueError):
        SK.build_sketch(G, method="random_sampling", k=2)


# ---------------------------------------------------------------------------
# Appendix A: Error(S_G, S_Gk) <= ||G G^T - G_k G_k^T||  (Lemma A.1)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), st.sampled_from(
    ["top_outputs", "random_sampling", "random_projection", "truncated_svd"]))
def test_lemma_a1_bound(seed, method):
    rng = np.random.default_rng(seed)
    n, d, k = 20, 9, 3
    G = rand_G(rng, n, d, spiky=seed % 2 == 0)
    Gk = np.asarray(SK.build_sketch(jnp.asarray(G), method=method, k=k,
                                    key=jax.random.key(seed)),
                    dtype=np.float64)
    op_norm = np.linalg.norm(G.astype(np.float64) @ G.T - Gk @ Gk.T, ord=2)
    for _ in range(32):                      # sampled leaves (sup unreachable)
        vR = (rng.random(n) < rng.random()).astype(np.float64)
        if vR.sum() == 0:
            continue
        err = abs(score(G, vR) - score(Gk, vR))
        assert err <= op_norm * 1.0001 + 1e-5


def test_svd_error_bound_sigma_k1(rng):
    """Prop A.2: Error <= sigma_{k+1}^2(G) for the truncated-SVD sketch."""
    G = rand_G(rng, 32, 8)
    k = 4
    Gk = np.asarray(SK.build_sketch(jnp.asarray(G), method="truncated_svd",
                                    k=k), dtype=np.float64)
    s = np.linalg.svd(G, compute_uv=False)
    bound = s[k] ** 2
    for seed in range(64):
        r = np.random.default_rng(seed)
        vR = (r.random(32) < 0.5).astype(np.float64)
        if vR.sum() == 0:
            continue
        assert abs(score(G, vR) - score(Gk, vR)) <= bound * 1.001 + 1e-4


def test_top_outputs_error_bound(rng):
    """Prop A.3: Error <= sum_{j>k} ||g_ij||^2."""
    G = rand_G(rng, 24, 10, spiky=True)
    k = 4
    Gk = np.asarray(SK.build_sketch(jnp.asarray(G), method="top_outputs",
                                    k=k), dtype=np.float64)
    norms = np.sort(np.sum(G.astype(np.float64) ** 2, axis=0))[::-1]
    bound = norms[k:].sum()
    for seed in range(64):
        r = np.random.default_rng(seed)
        vR = (r.random(24) < 0.5).astype(np.float64)
        if vR.sum() == 0:
            continue
        assert abs(score(G, vR) - score(Gk, vR)) <= bound * 1.001 + 1e-4


# ---------------------------------------------------------------------------
# Sharded sketch == single-device sketch (1-device mesh exercises the psum path)
# ---------------------------------------------------------------------------

def test_sketch_sharded_matches_single_device(rng):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1), ("data", "model"))
    G = jnp.asarray(rand_G(rng, 32, 8))
    for method in ("top_outputs", "random_projection", "none"):
        key = jax.random.key(7)

        def local(Gl):
            return SK.sketch_sharded(Gl, method=method, k=3, key=key,
                                     d_global=8)

        out = jax.jit(shard_map(local, mesh=mesh,
                                in_specs=(P("data", "model"),),
                                out_specs=P("data", None),
                                check_vma=False))(G)
        ref = SK.build_sketch(G, method=method, k=3, key=key)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
