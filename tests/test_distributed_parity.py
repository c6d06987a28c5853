"""Multi-device parity suite: distributed training == single-device training.

Runs under 8 emulated CPU devices (conftest.py sets
``--xla_force_host_platform_device_count=8`` before jax initialises) on a
(4, 2) ``(data, model)`` mesh, and pins down the numerics contract of
`core.distributed` against the single-device `boosting.boost_step`:

* **Structure is bitwise.**  Split decisions (feat/thr, leaf-wise topology,
  smaller-child choices) match the single-device grower exactly, because the
  distributed grower psums *integer* per-node counts and takes the argmax of
  gains computed from the same psummed histograms on every shard.
* **Values are bitwise on dyadic fixtures.**  fp32 additions of
  dyadic-valued gradients (multiples of 1/4) are exact regardless of
  association, so a single-round ``multitask_mse`` fit on dyadic targets is
  bit-identical end to end — predictions, leaf values, covers — for *all
  five* sketch methods and both growth modes.  This is the strongest
  machine-checkable statement of "the collective changes nothing".
* **Generic floats are allclose.**  On arbitrary data the psum re-associates
  fp32 sums (local partial + tree-reduce vs one long segment_sum), so values
  drift by ~1e-6/round; structure still matches except where two candidate
  splits have gains within an ulp of each other (ties).  ``truncated_svd``
  additionally runs eigh on the psummed Gram matrix, which under a
  near-degenerate spectrum may rotate the sketch subspace — so for that
  method multi-round parity is asserted at the loss level only.  So is
  ``random_sampling`` under ``single_tree``: this fixture holds an exact
  tie in round 0 that the two sum orders break differently.
* **Sketched collectives** (``dist_hist_compression="sketch"``) are exactly
  the exact psum when the channel count fits the JL width (pass-through),
  and within a documented drift envelope otherwise (count channel always
  exact; leaf values never sketched).

See docs/distributed.md for the full derivation.
"""
import dataclasses

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.core import distributed as GD
from repro.core import losses as L
from repro.core import quantize as Q
from repro.core.boosting import GBDTConfig, boost_step
from repro.data.pipeline import make_tabular
from repro.launch.mesh import make_mesh

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs >= 8 (emulated) devices; tests/conftest.py sets "
           "XLA_FLAGS=--xla_force_host_platform_device_count=8")

N, M, D, BINS = 256, 6, 8, 16
SKETCHES = ("none", "top_outputs", "random_sampling", "random_projection",
            "truncated_svd")
# Methods whose distributed sketch matmul reduces to column selection plus a
# psum of exact zeros — bitwise-stable even on generic float data.  The dense
# projections (random_projection, truncated_svd) re-associate fp32 sums and
# are pinned by the dyadic fixtures instead.  random_sampling selects columns
# bit-exactly too, but scales them by a non-dyadic 1/sqrt(k p_i), so the
# psummed histogram sums re-associate like a dense projection's.
REASSOC_FREE = ("none", "top_outputs")


def _cfg(**kw):
    # Pin the sketch to the deterministic baseline: the config default
    # (random_projection, k=5) is reassociation-prone and would blur what a
    # test is actually asserting.  Parametrized tests override explicitly.
    base = dict(loss="multiclass", n_outputs=D, depth=3, n_bins=BINS,
                sketch_method="none", sketch_k=0,
                learning_rate=0.3, use_kernel=False, seed=0)
    base.update(kw)
    return GBDTConfig(**base)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((4, 2), ("data", "model"))


@pytest.fixture(scope="module")
def data():
    X, y = make_tabular("multiclass", N, M, D, seed=0)
    q = Q.fit_quantizer(X, BINS)
    return Q.apply_quantizer(q, jnp.asarray(X)), jnp.asarray(y)


@pytest.fixture(scope="module")
def dyadic_targets():
    # Multiples of 1/4: fp32 sums of a few hundred of these are exact, so
    # every reduction order gives the same bits.
    rng = np.random.default_rng(1)
    return jnp.asarray(np.round(rng.normal(size=(N, D)) * 4) / 4, jnp.float32)


def _run_pair(cfg, codes, Y, mesh, *, rounds=1, feature_shard=False):
    """(single-device, distributed) fits from the same keys; returns
    (F_single, F_dist, trees_single, trees_dist)."""
    step = GD.make_distributed_boost_step(mesh, cfg,
                                          feature_shard=feature_shard)
    # Both steps donate F: each path needs its own buffer.
    F1 = jnp.zeros((N, D), jnp.float32)
    F2 = jnp.zeros((N, D), jnp.float32)
    key = jax.random.key(0)
    t1s, t2s = [], []
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        F1, t1 = boost_step(F1, codes, Y, sub, cfg)
        F2, t2 = step(F2, codes, Y, sub)
        t1s.append(t1)
        t2s.append(t2)
    return F1, F2, t1s, t2s


def _struct_equal(a, b, fields=("feat", "thr")):
    return all(np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))) for f in fields)


# ---------------------------------------------------------------------------
# Multi-round parity on generic float data.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", ("single_tree", "one_vs_all"))
@pytest.mark.parametrize("method", SKETCHES)
def test_levelwise_multiround_parity(method, strategy, mesh, data):
    codes, Y = data
    cfg = _cfg(strategy=strategy, sketch_method=method,
               sketch_k=0 if method == "none" else 3)
    F1, F2, t1s, t2s = _run_pair(cfg, codes, Y, mesh, rounds=3)
    lv = L.get_loss("multiclass").value
    if method == "truncated_svd" or (method == "random_sampling"
                                     and strategy == "single_tree"):
        # eigh(psummed Gram) can rotate the sketch under near-degenerate
        # spectra: the two fits are different-but-equally-good models.
        # random_sampling's sketch is bit-equal on both paths, but its
        # non-dyadic 1/sqrt(k p_i) scale makes the row sums re-associate,
        # and this fixture holds an exact tie: in round 0, node 6 splits on
        # (feat 4, thr 5) on one device and on (3, 13) on the mesh.  Both
        # have the float64 gain 3.263571786147441; float32 reads 3.2635746
        # and 3.2635708.  The two partitions differ in 2 of the node's 54
        # rows, so the full-gradient leaf values move F by up to 1.51 and
        # the later rounds part ways.
        l1, l2 = float(lv(F1, Y)), float(lv(F2, Y))
        l0 = float(lv(jnp.zeros_like(F1), Y))
        assert l1 < l0 and l2 < l0
        assert abs(l1 - l2) <= 0.25 * max(l1, l2)
        return
    np.testing.assert_allclose(np.asarray(F1), np.asarray(F2),
                               rtol=1e-5, atol=2e-5)
    if strategy == "single_tree" and method in REASSOC_FREE:
        for a, b in zip(t1s, t2s):
            assert _struct_equal(a, b)


@pytest.mark.parametrize("method,rounds", [("none", 3), ("top_outputs", 2)])
def test_leafwise_multiround_structural(method, rounds, mesh, data):
    # top_outputs stops at 2 rounds: by round 3 the ulp-level F drift flips
    # an exactly-tied (feat, thr) pair (duplicate features in the synthetic
    # data) — the documented tie caveat, not a structure bug.
    codes, Y = data
    cfg = _cfg(growth="leafwise", max_leaves=6, sketch_method=method,
               sketch_k=0 if method == "none" else 3)
    F1, F2, t1s, t2s = _run_pair(cfg, codes, Y, mesh, rounds=rounds)
    for a, b in zip(t1s, t2s):
        assert _struct_equal(a, b, ("feat", "thr", "left", "right",
                                    "node_count"))
    np.testing.assert_allclose(np.asarray(F1), np.asarray(F2),
                               rtol=1e-5, atol=2e-5)


@pytest.mark.parametrize("growth,max_leaves", [("levelwise", 0),
                                               ("leafwise", 6)])
def test_one_vs_all_first_round_bitwise(growth, max_leaves, mesh, data):
    codes, Y = data
    cfg = _cfg(strategy="one_vs_all", growth=growth, max_leaves=max_leaves,
               sketch_method="none", sketch_k=0)
    F1, F2, t1s, t2s = _run_pair(cfg, codes, Y, mesh, rounds=1)
    assert _struct_equal(t1s[0], t2s[0])
    np.testing.assert_allclose(np.asarray(t1s[0].gain),
                               np.asarray(t2s[0].gain), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(F1), np.asarray(F2),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("method", ("none", "top_outputs"))
def test_feature_shard_parity(method, mesh, data):
    codes, Y = data
    cfg = _cfg(sketch_method=method, sketch_k=0 if method == "none" else 3)
    F1, F2, t1s, t2s = _run_pair(cfg, codes, Y, mesh, rounds=2,
                                 feature_shard=True)
    for a, b in zip(t1s, t2s):
        assert _struct_equal(a, b)
    np.testing.assert_allclose(np.asarray(F1), np.asarray(F2),
                               rtol=1e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Bit-identical fits on dyadic fixtures — all 5 methods, both growth modes.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("growth,max_leaves", [("levelwise", 0),
                                               ("leafwise", 6)])
@pytest.mark.parametrize("method", SKETCHES)
def test_single_round_dyadic_bitwise(method, growth, max_leaves, mesh, data,
                                     dyadic_targets):
    codes, _ = data
    cfg = _cfg(loss="multitask_mse", growth=growth, max_leaves=max_leaves,
               sketch_method=method, sketch_k=0 if method == "none" else 3,
               learning_rate=0.5)
    F1, F2, t1s, t2s = _run_pair(cfg, codes, dyadic_targets, mesh, rounds=1)
    t1, t2 = t1s[0], t2s[0]
    # Predictions, leaf values and covers: bit-identical for every method.
    assert np.array_equal(np.asarray(F1), np.asarray(F2))
    assert np.array_equal(np.asarray(t1.value), np.asarray(t2.value))
    assert np.array_equal(np.asarray(t1.cover), np.asarray(t2.cover))
    np.testing.assert_allclose(np.asarray(t1.gain), np.asarray(t2.gain),
                               rtol=1e-4, atol=1e-5)
    if method not in ("truncated_svd", "random_sampling"):
        # truncated_svd's sketch values are non-dyadic (Gaussian-ish Pi), and
        # so are random_sampling's (a 1/sqrt(k p_i) column scale), so
        # histogram reassociation can flip exactly-tied (feat, thr) pairs
        # that induce the same partition; the fit above proves the partition
        # is identical either way.
        assert _struct_equal(t1, t2)
    if growth == "leafwise":
        assert _struct_equal(t1, t2, ("left", "right", "node_count"))


# ---------------------------------------------------------------------------
# Sketched histogram collective.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("growth,max_leaves", [("levelwise", 0),
                                               ("leafwise", 6)])
def test_sketched_collective_passthrough_is_exact(growth, max_leaves, mesh,
                                                  data):
    # dist_hist_k >= gradient channels (= D here, sketch 'none') makes the
    # compressor the identity: the trees must match the exact collective bit
    # for bit.
    codes, Y = data
    cfg_ex = _cfg(growth=growth, max_leaves=max_leaves)
    cfg_sk = dataclasses.replace(cfg_ex, dist_hist_compression="sketch",
                                 dist_hist_k=D)
    s_ex = GD.make_distributed_boost_step(mesh, cfg_ex)
    s_sk = GD.make_distributed_boost_step(mesh, cfg_sk)
    Fe = jnp.zeros((N, D), jnp.float32)
    Fs = jnp.zeros((N, D), jnp.float32)
    key = jax.random.key(0)
    for _ in range(2):
        key, sub = jax.random.split(key)
        Fe, te = s_ex(Fe, codes, Y, sub)
        Fs, ts = s_sk(Fs, codes, Y, sub)
        assert _struct_equal(te, ts)
        assert np.array_equal(np.asarray(te.value), np.asarray(ts.value))
    assert np.array_equal(np.asarray(Fe), np.asarray(Fs))


@pytest.mark.parametrize("growth,max_leaves", [("levelwise", 0),
                                               ("leafwise", 6)])
def test_sketched_collective_drift_envelope(growth, max_leaves, mesh, data):
    # Lossy width (6 of 8 channels): split decisions may differ, but the
    # count channel is exact and leaf values are never sketched, so the fit
    # must stay a comparably-good model — the documented drift envelope.
    codes, Y = data
    cfg_ex = _cfg(growth=growth, max_leaves=max_leaves)
    cfg_sk = dataclasses.replace(cfg_ex, dist_hist_compression="sketch",
                                 dist_hist_k=6)
    s_ex = GD.make_distributed_boost_step(mesh, cfg_ex)
    s_sk = GD.make_distributed_boost_step(mesh, cfg_sk)
    Fe = jnp.zeros((N, D), jnp.float32)
    Fs = jnp.zeros((N, D), jnp.float32)
    key = jax.random.key(0)
    for _ in range(5):
        key, sub = jax.random.split(key)
        Fe, _ = s_ex(Fe, codes, Y, sub)
        Fs, _ = s_sk(Fs, codes, Y, sub)
    lv = L.get_loss("multiclass").value
    l0 = float(lv(jnp.zeros((N, D), jnp.float32), Y))
    le, ls = float(lv(Fe, Y)), float(lv(Fs, Y))
    assert np.isfinite(np.asarray(Fs)).all()
    assert ls < l0                       # the compressed fit still learns
    assert ls <= 1.5 * le                # ... and stays near the exact fit


def test_collective_bytes_model(mesh):
    # The analytic payload model the bench asserts against: compression
    # moves <= (k+1)/(d+1) of the exact collective's bytes.
    cfg_ex = _cfg()
    cfg_sk = dataclasses.replace(cfg_ex, dist_hist_compression="sketch",
                                 dist_hist_k=5)
    ex = GD.round_collective_bytes(cfg_ex, M, D)
    sk = GD.round_collective_bytes(cfg_sk, M, D)
    assert ex["moved_bytes"] == ex["exact_bytes"]
    assert sk["hist_cells"] == ex["hist_cells"]
    assert sk["moved_bytes"] < sk["exact_bytes"]
    assert sk["moved_bytes"] <= (5 + 1) / (D + 1) * sk["full_bytes"] * (1 + 1e-9)


# ---------------------------------------------------------------------------
# Distributed eval + fit driver.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("loss,task", [("multiclass", "multiclass"),
                                       ("multilabel", "multilabel"),
                                       ("multitask_mse", "multitask_mse")])
def test_eval_parity(loss, task, mesh):
    X, y = make_tabular(task, N, M, D, seed=2)
    Y = jnp.asarray(y)
    rng = np.random.default_rng(3)
    F = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    cfg = _cfg(loss=loss)
    evaluate = GD.make_distributed_eval(mesh, cfg)
    got = float(evaluate(F, Y))
    want = float(L.get_loss(loss).value(F, Y))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_fit_distributed_matches_single_loop(mesh, data):
    codes, Y = data
    cfg = _cfg(n_trees=3, growth="leafwise", max_leaves=6, seed=7)
    F_d, forest, history = GD.fit_distributed(cfg, mesh, codes, Y,
                                              eval_every=1)
    # The reference: the exact key schedule fit_distributed documents.
    F_s = jnp.zeros((N, D), jnp.float32)
    key = jax.random.key(cfg.seed)
    for _ in range(3):
        key, sub = jax.random.split(key)
        F_s, _ = boost_step(F_s, codes, Y, sub, cfg)
    np.testing.assert_allclose(np.asarray(F_d), np.asarray(F_s),
                               rtol=1e-5, atol=2e-5)
    assert forest.feat.shape[0] == 3             # stacked round axis
    assert [h["round"] for h in history] == [0, 1, 2]
    assert history[-1]["train_loss"] < history[0]["train_loss"]


def test_fit_distributed_requires_n_outputs(mesh, data):
    codes, Y = data
    cfg = dataclasses.replace(_cfg(), n_outputs=0)
    with pytest.raises(ValueError, match="n_outputs"):
        GD.fit_distributed(cfg, mesh, codes, Y)


# ---------------------------------------------------------------------------
# Config validation: lifted rejections train, real misuses fail loudly.
# ---------------------------------------------------------------------------

def test_leafwise_distributed_factory_accepts(mesh):
    # Regression: the factory used to reject growth='leafwise' outright.
    step = GD.make_distributed_boost_step(
        mesh, _cfg(growth="leafwise", max_leaves=4))
    assert callable(step)


def test_bf16_distributed_trains(mesh, data):
    # Regression: the factory used to reject hist_dtype='bfloat16'.  The
    # distributed path rounds the stats once per round, mirroring the
    # kernel's per-tile rounding, so the standard bf16 config trains.
    codes, Y = data
    cfg = _cfg(hist_dtype="bfloat16", use_kernel="interpret")
    step = GD.make_distributed_boost_step(mesh, cfg)
    F = jnp.zeros((N, D), jnp.float32)
    key = jax.random.key(0)
    for _ in range(2):
        key, sub = jax.random.split(key)
        F, _ = step(F, codes, Y, sub)
    F = np.asarray(F)
    assert np.isfinite(F).all() and np.abs(F).max() > 0


def test_bf16_under_jnp_rejected(mesh):
    with pytest.raises(ValueError, match="bfloat16"):
        GD.make_distributed_boost_step(
            mesh, _cfg(hist_dtype="bfloat16", use_kernel=False))


def test_unknown_dist_hist_compression_rejected(mesh):
    with pytest.raises(ValueError, match="dist_hist_compression"):
        GD.make_distributed_boost_step(
            mesh, _cfg(dist_hist_compression="gzip"))


def test_negative_dist_hist_k_rejected(mesh):
    with pytest.raises(ValueError, match="dist_hist_k"):
        GD.make_distributed_boost_step(
            mesh, _cfg(dist_hist_compression="sketch", dist_hist_k=-1))


def test_sketch_compression_needs_width(mesh):
    with pytest.raises(ValueError, match="dist_hist_k"):
        GD.make_distributed_boost_step(
            mesh, _cfg(dist_hist_compression="sketch", dist_hist_k=0,
                       sketch_k=0))


def test_single_device_rejects_dist_knob():
    # resolve() is the single-device validation gate (SketchBoost.fit runs
    # it before training): the collective knob must fail loudly there.
    cfg = _cfg(dist_hist_compression="sketch", dist_hist_k=4)
    with pytest.raises(ValueError, match="single-device"):
        cfg.resolve(D)


@pytest.mark.parametrize("use_kernel", ["interpret", "pallas"])
def test_grow_tree_rejects_kernel_with_collectives(use_kernel, data):
    # The fused kernel level op has no point between its tiles and split
    # kernels for the row psum, so the sharded grower is jnp only.
    from repro.core import tree as T
    codes, _ = data
    stats = jnp.ones((N, 2), jnp.float32)
    G = jnp.zeros((N, 1), jnp.float32)
    with pytest.raises(ValueError, match="collectives"):
        T.grow_tree(codes, stats, G, G, depth=2, n_bins=BINS, lam=1.0,
                    use_kernel=use_kernel, psum_axes=("data",))


def test_feature_shard_one_vs_all_rejected(mesh):
    with pytest.raises(ValueError, match="one_vs_all"):
        GD.make_distributed_boost_step(mesh, _cfg(strategy="one_vs_all"),
                                       feature_shard=True)


def test_feature_shard_leafwise_rejected(mesh):
    with pytest.raises(ValueError, match="leaf-wise"):
        GD.make_distributed_boost_step(
            mesh, _cfg(growth="leafwise", max_leaves=4), feature_shard=True)


def test_feature_shard_indivisible_features_rejected(mesh, data):
    _, Y = data
    codes7 = jnp.zeros((N, 7), jnp.uint8)
    step = GD.make_distributed_boost_step(mesh, _cfg(), feature_shard=True)
    with pytest.raises(ValueError, match="divisible"):
        step(jnp.zeros((N, D), jnp.float32), codes7, Y, jax.random.key(0))
