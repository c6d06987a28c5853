"""GBDT substrate: quantizer, losses, histograms, splits, trees, boosting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import histogram as H
from repro.core import losses as L
from repro.core import quantize as Q
from repro.core import split as S
from repro.core import tree as T
from repro.core.boosting import GBDTConfig, SketchBoost
from repro.data.pipeline import make_tabular, train_test_split


# ---------------------------------------------------------------------------
# Quantizer
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(8, 64))
def test_quantizer_codes_in_range_and_monotone(seed, n_bins):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(200, 3)).astype(np.float32)
    q = Q.fit_quantizer(X, n_bins)
    codes = np.asarray(Q.apply_quantizer(q, jnp.asarray(X)))
    assert codes.min() >= 0 and codes.max() < n_bins
    # Monotone: larger feature value -> same-or-larger code.
    for j in range(3):
        order = np.argsort(X[:, j])
        assert (np.diff(codes[order, j].astype(int)) >= 0).all()


def test_quantizer_handles_nan():
    X = np.array([[1.0], [np.nan], [2.0], [3.0]], np.float32)
    q = Q.fit_quantizer(X, 8)
    codes = np.asarray(Q.apply_quantizer(q, jnp.asarray(X)))
    assert codes.shape == (4, 1)
    assert codes.min() >= 0


# ---------------------------------------------------------------------------
# Losses: gradients/Hessians match autodiff
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loss_name,d", [("multiclass", 5),
                                         ("multilabel", 4),
                                         ("multitask_mse", 3)])
def test_loss_grad_hess_match_autodiff(loss_name, d):
    rng = np.random.default_rng(0)
    n = 16
    F = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    if loss_name == "multiclass":
        Y = jnp.asarray(rng.integers(0, d, n).astype(np.int32))
    elif loss_name == "multilabel":
        Y = jnp.asarray((rng.random((n, d)) < 0.5).astype(np.float32))
    else:
        Y = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    loss = L.get_loss(loss_name)
    G, Hd = loss.grad_hess(F, Y)
    # value() is a mean; grad_hess is per-element.  d(total)/dF == G.
    n_elems = n if loss_name == "multiclass" else n * d
    G_auto = jax.grad(lambda F_: loss.value(F_, Y) * n_elems)(F)
    np.testing.assert_allclose(np.asarray(G), np.asarray(G_auto),
                               rtol=1e-3, atol=1e-4)
    assert np.all(np.asarray(Hd) >= 0)       # diagonal Hessian PSD


# ---------------------------------------------------------------------------
# Histograms & leaf sums
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_histogram_matches_bruteforce(seed):
    rng = np.random.default_rng(seed)
    n, m, B, nodes, c = 64, 4, 8, 4, 3
    codes = rng.integers(0, B, (n, m)).astype(np.int32)
    node = rng.integers(0, nodes, n).astype(np.int32)
    stats = rng.normal(size=(n, c)).astype(np.float32)
    hist = np.asarray(H.build_histograms_jnp(jnp.asarray(codes),
                                             jnp.asarray(node),
                                             jnp.asarray(stats),
                                             n_nodes=nodes, n_bins=B))
    ref = np.zeros((nodes, m, B, c), np.float32)
    for i in range(n):
        for f in range(m):
            ref[node[i], f, codes[i, f]] += stats[i]
    np.testing.assert_allclose(hist, ref, atol=1e-4)


def test_leaf_sums():
    rng = np.random.default_rng(1)
    n, d, leaves = 50, 4, 8
    pos = rng.integers(0, leaves, n).astype(np.int32)
    G = rng.normal(size=(n, d)).astype(np.float32)
    Hd = np.abs(rng.normal(size=(n, d))).astype(np.float32)
    gs, hs = H.leaf_sums(jnp.asarray(pos), jnp.asarray(G), jnp.asarray(Hd),
                         n_leaves=leaves)
    for j in range(leaves):
        np.testing.assert_allclose(np.asarray(gs)[j], G[pos == j].sum(0),
                                   atol=1e-4)
        np.testing.assert_allclose(np.asarray(hs)[j], Hd[pos == j].sum(0),
                                   atol=1e-4)


# ---------------------------------------------------------------------------
# Split search vs brute force
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_best_split_matches_bruteforce(seed):
    rng = np.random.default_rng(seed)
    n, m, B, k = 48, 3, 6, 2
    codes = rng.integers(0, B, (n, m)).astype(np.int32)
    stats = np.concatenate([rng.normal(size=(n, k)).astype(np.float32),
                            np.ones((n, 1), np.float32)], axis=1)
    lam = 1.0
    hist = H.build_histograms_jnp(jnp.asarray(codes),
                                  jnp.zeros(n, jnp.int32),
                                  jnp.asarray(stats), n_nodes=1, n_bins=B)
    gain = S.split_scores(hist, jnp.float32(lam), jnp.float32(0.0))
    sp = S.best_splits(gain)
    bf_feat, bf_thr, bf_gain = S.brute_force_best_split(codes, stats, lam)
    assert float(sp.gain[0]) == pytest.approx(bf_gain, rel=1e-4)
    # Argmax ties can differ; the achieved gain is the contract.


# ---------------------------------------------------------------------------
# Tree growth / routing invariants
# ---------------------------------------------------------------------------

def _grow(seed=0, n=128, m=5, d=3, depth=3):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, 16, (n, m)).astype(np.uint8)
    G = rng.normal(size=(n, d)).astype(np.float32)
    Hd = np.ones((n, d), np.float32)
    stats = np.concatenate([G, np.ones((n, 1), np.float32)], 1)
    tree, pos = T.grow_tree(jnp.asarray(codes), jnp.asarray(stats),
                            jnp.asarray(G), jnp.asarray(Hd), depth=depth,
                            n_bins=16, lam=1.0)
    return codes, G, tree, np.asarray(pos)


def test_tree_routing_consistent():
    codes, G, tree, pos = _grow()
    pos2 = np.asarray(T.tree_leaf_index(tree.feat, tree.thr,
                                        jnp.asarray(codes), depth=3))
    np.testing.assert_array_equal(pos, pos2)
    assert pos.min() >= 0 and pos.max() < 2 ** 3


def test_leaf_values_are_newton_step():
    codes, G, tree, pos = _grow()
    lam = 1.0
    vals = np.asarray(tree.value)
    for leaf in np.unique(pos):
        sel = pos == leaf
        expect = -G[sel].sum(0) / (sel.sum() + lam)
        np.testing.assert_allclose(vals[leaf], expect, rtol=1e-4, atol=1e-5)


def test_route_level_semantics():
    codes = jnp.asarray([[3], [7]], jnp.uint8)
    pos = jnp.zeros(2, jnp.int32)
    new = T.route_level(codes, pos, jnp.asarray([0]), jnp.asarray([5]))
    np.testing.assert_array_equal(np.asarray(new), [0, 1])  # 3<=5 L, 7>5 R


# ---------------------------------------------------------------------------
# Boosting end-to-end
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("task,loss", [("multiclass", "multiclass"),
                                       ("multilabel", "multilabel"),
                                       ("multitask_mse", "multitask_mse")])
def test_boosting_improves_over_base(task, loss):
    X, y = make_tabular(task, 1200, 15, 5, seed=3)
    Xtr, Xte, ytr, yte = train_test_split(X, y, seed=3)
    cfg = GBDTConfig(loss=loss, n_trees=25, depth=4, learning_rate=0.3,
                     sketch_method="random_projection", sketch_k=3)
    m = SketchBoost(cfg).fit(Xtr, ytr)
    fitted = m.eval_loss(Xte, yte)
    # Base = constant prediction at the prior (1 tree, lr=0).
    base = SketchBoost(GBDTConfig(loss=loss, n_trees=1, depth=1,
                                  learning_rate=0.0)).fit(Xtr, ytr)
    base_loss = base.eval_loss(Xte, yte)
    assert fitted < base_loss, (fitted, base_loss)


@pytest.mark.parametrize("method", ["none", "top_outputs", "random_sampling",
                                    "random_projection", "truncated_svd"])
def test_all_sketch_methods_train(method):
    X, y = make_tabular("multiclass", 800, 10, 6, seed=1)
    cfg = GBDTConfig(loss="multiclass", n_trees=10, depth=3,
                     learning_rate=0.3, sketch_method=method, sketch_k=2)
    m = SketchBoost(cfg).fit(X, y)
    assert np.isfinite(m.eval_loss(X, y))


def test_one_vs_all_strategy():
    X, y = make_tabular("multiclass", 800, 10, 4, seed=2)
    cfg = GBDTConfig(loss="multiclass", strategy="one_vs_all", n_trees=10,
                     depth=3, learning_rate=0.3)
    m = SketchBoost(cfg).fit(X, y)
    p = np.asarray(m.predict(X))
    assert p.shape == (800, 4)
    np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-4)   # softmax outputs
    assert (p.argmax(1) == y).mean() > 0.5


def test_early_stopping_truncates_forest():
    X, y = make_tabular("multiclass", 600, 8, 3, seed=4)
    Xtr, Xte, ytr, yte = train_test_split(X, y, seed=4)
    cfg = GBDTConfig(loss="multiclass", n_trees=60, depth=3,
                     learning_rate=1.0, early_stopping_rounds=5)
    m = SketchBoost(cfg).fit(Xtr, ytr, eval_set=(Xte, yte))
    assert m.forest.n_trees <= 60
    assert m.best_round < 60


def test_sgb_goss_colsample_paths():
    X, y = make_tabular("multiclass", 600, 10, 3, seed=5)
    for kw in (dict(subsample=0.7), dict(goss_a=0.2, goss_b=0.2),
               dict(colsample=0.5)):
        cfg = GBDTConfig(loss="multiclass", n_trees=8, depth=3,
                         learning_rate=0.3, **kw)
        m = SketchBoost(cfg).fit(X, y)
        assert np.isfinite(m.eval_loss(X, y))


def test_predict_matches_incremental_F():
    """predict_raw(Xtr) must equal the training-time F trajectory."""
    X, y = make_tabular("multiclass", 400, 8, 4, seed=6)
    cfg = GBDTConfig(loss="multiclass", n_trees=12, depth=3,
                     learning_rate=0.2, sketch_method="none")
    m = SketchBoost(cfg).fit(X, y)
    F_pred = np.asarray(m.predict_raw(X))
    # Recompute by replaying the forest.
    codes = m._bin(X)
    F_replay = np.asarray(T.predict_forest(m.forest, codes,
                                           cfg.learning_rate, m.base_score))
    np.testing.assert_allclose(F_pred, F_replay, rtol=1e-5, atol=1e-5)


def test_kernel_path_matches_jnp_path():
    """use_kernel=True (Pallas interpret) trains to identical trees."""
    X, y = make_tabular("multiclass", 300, 6, 3, seed=7)
    kw = dict(loss="multiclass", n_trees=3, depth=3, learning_rate=0.3,
              sketch_method="top_outputs", sketch_k=2)
    m1 = SketchBoost(GBDTConfig(use_kernel="jnp", **kw)).fit(X, y)
    m2 = SketchBoost(GBDTConfig(use_kernel=True, **kw)).fit(X, y)
    np.testing.assert_array_equal(np.asarray(m1.forest.feat),
                                  np.asarray(m2.forest.feat))
    np.testing.assert_allclose(np.asarray(m1.forest.value),
                               np.asarray(m2.forest.value),
                               rtol=1e-4, atol=1e-5)


def test_use_kernel_interpret_end_to_end():
    """Full fit through BOTH Pallas kernels (histogram tiles + split-scan)
    in interpret mode, on the default engine: functionally equivalent to
    the jnp path.

    The comparison is on predictions, not tree structure: the synthetic
    generator emits duplicated features whose splits tie *exactly*, and the
    kernel's (algebraically equal) accumulation order may break such ties
    toward the twin feature.  Exact per-histogram arg-max parity is asserted
    in tests/test_kernels.py on shared inputs.
    """
    X, y = make_tabular("multiclass", 250, 6, 3, seed=8)
    kw = dict(loss="multiclass", n_trees=3, depth=3, learning_rate=0.3,
              n_bins=32, sketch_method="top_outputs", sketch_k=2)
    m_jnp = SketchBoost(GBDTConfig(use_kernel="jnp", **kw)).fit(X, y)
    m_ker = SketchBoost(GBDTConfig(use_kernel="interpret", **kw)).fit(X, y)
    np.testing.assert_allclose(np.asarray(m_ker.predict_raw(X)),
                               np.asarray(m_jnp.predict_raw(X)),
                               rtol=1e-3, atol=1e-3)
    assert m_ker.eval_loss(X, y) == pytest.approx(m_jnp.eval_loss(X, y),
                                                 rel=1e-3)
    p = np.asarray(m_ker.predict(X))
    assert np.all(np.isfinite(p))
    np.testing.assert_allclose(p.sum(1), 1.0, atol=1e-4)


def test_kernel_mode_resolution():
    import jax as _jax
    assert H.resolve_kernel_mode(False) == "jnp"
    assert H.resolve_kernel_mode("interpret") == "interpret"
    assert H.resolve_kernel_mode("pallas") == "pallas"
    auto = H.resolve_kernel_mode(True)
    if _jax.default_backend() == "tpu":
        assert auto == "pallas"
    else:
        assert auto in ("jnp", "interpret")   # env-dependent off-TPU
    with pytest.raises(ValueError):
        H.resolve_kernel_mode("mosaic")
    # config resolution pins the mode so jit cache keys see a concrete string
    cfg = GBDTConfig(use_kernel=True).resolve(4)
    assert cfg.use_kernel in ("jnp", "pallas", "interpret")
