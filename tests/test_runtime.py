"""Runtime substrate: checkpointing, restart, elastic re-mesh, compression,
optimizer, data pipeline, serving."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.io.checkpoint import CheckpointManager
from repro.runtime.elastic import rebalance_batch, remesh
from repro.runtime.fault import RestartableLoop, StragglerWatchdog
from repro.training import optimizer as opt


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------

def _state(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.normal(size=(8, 4)).astype(np.float32)),
            "opt": {"m": jnp.zeros((8, 4)), "step": jnp.int32(3)},
            "nested": [jnp.arange(5), jnp.float32(2.5)]}


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    state = _state()
    mgr.save(7, state, metadata={"note": "x"})
    restored, step = mgr.restore(state)
    assert step == 7
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    assert mgr.manifest(7)["metadata"]["note"] == "x"


def test_checkpoint_keep_n_gc(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=2, async_save=False)
    for s in (1, 2, 3, 4):
        mgr.save(s, _state(s))
    assert mgr.all_steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_async_then_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(1, _state())
    mgr.wait()
    assert mgr.latest_step() == 1


def test_restartable_loop_resumes(tmp_path):
    """Kill after N steps; resume must continue the same trajectory."""
    def step_fn(state, batch):
        w, i = state
        return (w + batch, i + 1), {"w_sum": float(jnp.sum(w))}

    batches = [jnp.float32(x) for x in range(10)]
    loop1 = RestartableLoop(str(tmp_path), step_fn, save_every=2,
                            async_save=False)
    state1, n1 = loop1.run((jnp.float32(0.0), 0), iter(batches[:5]), 5)

    # restart: fresh loop resumes from latest checkpoint (step 4 saved)
    loop2 = RestartableLoop(str(tmp_path), step_fn, save_every=2,
                            async_save=False)
    resumed, start = loop2.resume_or_init((jnp.float32(0.0), 0))
    assert start == 5
    state2, n2 = loop2.run((jnp.float32(0.0), 0), iter(batches[5:]), 10)
    # Full-run reference
    w = 0.0
    for b in range(10):
        w += b
    assert float(state2[0]) == pytest.approx(w)


def test_straggler_watchdog_flags_outlier():
    wd = StragglerWatchdog(window=8, threshold=2.0)
    flags = [wd.observe(0.1) for _ in range(8)]
    assert not any(flags)
    assert wd.observe(1.0)


# ---------------------------------------------------------------------------
# Elastic re-mesh
# ---------------------------------------------------------------------------

def test_remesh_roundtrip_single_device():
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    tree = _state()
    sh = jax.tree.map(lambda x: NamedSharding(mesh, P()), tree)
    moved = remesh(tree, sh)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(moved)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))


def test_rebalance_batch():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"))
    assert rebalance_batch(37, mesh) == 37
    # fake larger dp via shape dict semantics is covered in dryrun


# ---------------------------------------------------------------------------
# Sketched gradient compression (paper Sec 3.3 -> DP all-reduce)
# ---------------------------------------------------------------------------

def test_compress_decompress_error_shrinks_with_k():
    from repro.distributed.compression import (compress_block,
                                               decompress_block)
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.normal(size=(64, 32)).astype(np.float32))
    errs = []
    for k in (2, 8, 32):
        sk, Pi, shape = compress_block(g, jax.random.key(1), k)
        rec = decompress_block(sk, Pi, shape)
        errs.append(float(jnp.linalg.norm(rec - g) / jnp.linalg.norm(g)))
    assert errs[0] > errs[1] > errs[2]


def test_sketched_psum_with_error_feedback_converges():
    """On a 1-device axis, sketched psum + error feedback must reconstruct the
    gradient on average: feeding the same gradient repeatedly with error
    feedback accumulates to the true direction."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.distributed.compression import sketched_psum
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("pod",))
    rng = np.random.default_rng(1)
    g = {"w": jnp.asarray(rng.normal(size=(32, 16)).astype(np.float32))}

    def run(gg, res, key):
        return sketched_psum(gg, key, "pod", k=4, residuals=res)

    f = jax.jit(shard_map(run, mesh=mesh,
                          in_specs=({"w": P()}, {"w": P()}, P()),
                          out_specs=({"w": P()}, {"w": P()}),
                          check_vma=False))
    acc = jnp.zeros_like(g["w"])
    res = {"w": jnp.zeros_like(g["w"])}
    for i in range(64):
        out, res = f(g, res, jax.random.key(i))
        acc = acc + out["w"]
    direction = acc / 64
    cos = float(jnp.sum(direction * g["w"]) /
                (jnp.linalg.norm(direction) * jnp.linalg.norm(g["w"])))
    assert cos > 0.7, cos


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["adamw", "adafactor", "sgd"])
def test_optimizer_descends_quadratic(name):
    cfg = opt.OptConfig(name=name, lr=0.1, warmup_steps=1, decay_steps=1000,
                        weight_decay=0.0, grad_clip=0.0)
    params = {"w": jnp.asarray(np.linspace(1, 2, 256,
                                           dtype=np.float32).reshape(16, 16))}
    state = opt.opt_init(params, cfg)
    loss = lambda p: 0.5 * jnp.sum(p["w"] ** 2)
    l0 = float(loss(params))
    for step in range(30):
        g = jax.grad(loss)(params)
        params, state = opt.opt_update(g, state, params, jnp.int32(step), cfg)
    assert float(loss(params)) < 0.5 * l0


def test_opt_abstract_matches_opt_init_structure():
    """The dry-run contract: abstract state must mirror opt_init exactly."""
    from repro.configs import smoke_config
    from repro.models import lm
    for name in ("adamw", "adafactor"):
        cfg = smoke_config("gemma-7b")
        ocfg = opt.OptConfig(name=name)
        params = lm.init(cfg, jax.random.key(0))
        real = opt.opt_init(params, ocfg)
        abs_ = opt.opt_abstract(lm.param_decls(cfg), ocfg)
        real_flat, real_def = jax.tree.flatten(real)
        abs_flat, abs_def = jax.tree.flatten(abs_)
        assert real_def == abs_def
        for r, a in zip(real_flat, abs_flat):
            assert r.shape == a.shape, (r.shape, a.shape)
            assert r.dtype == a.dtype


def test_grad_clip():
    g = {"w": jnp.full((4,), 10.0)}
    clipped, norm = opt.clip_by_global_norm(g, 1.0)
    assert float(jnp.linalg.norm(clipped["w"])) == pytest.approx(1.0, rel=1e-4)


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------

def test_tabular_generators():
    from repro.data.pipeline import make_tabular
    for task, check in [
        ("multiclass", lambda y: y.ndim == 1 and y.max() < 6),
        ("multilabel", lambda y: y.shape == (100, 6) and set(
            np.unique(y)) <= {0.0, 1.0}),
        ("multitask_mse", lambda y: y.shape == (100, 6)),
    ]:
        X, y = make_tabular(task, 100, 12, 6, seed=0)
        assert X.shape == (100, 12)
        assert check(y)


def test_lm_batches_and_prefetcher():
    from repro.data.pipeline import ShardedPrefetcher, lm_batches
    it = lm_batches(100, 4, 16, seed=0)
    pf = ShardedPrefetcher(it, process_index=0, process_count=1)
    b = next(pf)
    assert b["inputs"].shape == (4, 16)
    assert b["labels"].shape == (4, 16)
    assert int(jnp.max(b["labels"])) < 100
    pf.close()


def test_lm_batches_stub_embeddings():
    from repro.data.pipeline import lm_batches
    it = lm_batches(50, 2, 8, embed_dim=32, image_tokens=4, d_model=32)
    b = next(it)
    assert b["inputs"].shape == (2, 8, 32)
    assert b["image_embeds"].shape == (2, 4, 32)


# ---------------------------------------------------------------------------
# RestartableLoop on a real GBDT fit (the rewired fault-tolerance driver)
# ---------------------------------------------------------------------------

def _gbdt_fixture(seed=0):
    from repro.core.quantize import quantize_uniform
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.normal(size=(128, 6)).astype(np.float32))
    Y = jnp.asarray(rng.integers(0, 4, size=128), jnp.int32)
    return quantize_uniform(X, 16), Y


def test_restartable_loop_drives_gbdt_fit(tmp_path):
    """`fit_distributed` runs its round loop through RestartableLoop: a
    chaos kill mid-run leaves a round-boundary checkpoint, and resuming on
    the same mesh reproduces the uninterrupted run bit-for-bit."""
    import dataclasses
    from repro.core import distributed as GD
    from repro.core.boosting import GBDTConfig
    from repro.launch.mesh import make_mesh
    from repro.runtime.chaos import ChaosKill, KillAtRound

    codes, Y = _gbdt_fixture()
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = GBDTConfig(loss="multiclass", n_outputs=4, n_trees=5, depth=3,
                     n_bins=16, use_kernel=False, seed=3)
    F_ref, forest_ref, _ = GD.fit_distributed(cfg, mesh, codes, Y)

    ck = dataclasses.replace(cfg, save_every=2, ckpt_dir=str(tmp_path))
    with pytest.raises(ChaosKill):
        GD.fit_distributed(ck, mesh, codes, Y, chaos=KillAtRound(3))
    assert CheckpointManager(str(tmp_path)).latest_step() == 2

    rs = dataclasses.replace(ck, resume_from=str(tmp_path))
    F, forest, _ = GD.fit_distributed(rs, mesh, codes, Y)
    np.testing.assert_array_equal(np.asarray(F), np.asarray(F_ref))
    for a, b in zip(jax.tree.leaves(forest), jax.tree.leaves(forest_ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restartable_loop_virtual_delay_feeds_watchdog(tmp_path):
    """`DelayShard` adds virtual seconds to the watchdog's observations —
    deterministic straggler detection without sleeping."""
    from repro.runtime.chaos import DelayShard

    def step_fn(state, batch):
        return state + 1, {}

    wd = StragglerWatchdog(window=16, threshold=2.0)
    loop = RestartableLoop("", step_fn, save_every=0, chaos=DelayShard(10, 60.0),
                           watchdog=wd)
    _, n = loop.run(0, None, 12)
    assert n == 12
    assert wd.flagged >= 1          # the +60s virtual step is an outlier


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def test_forest_server_admission_alignment():
    """With admission knobs on, `serve` returns one slot per request —
    shed requests come back as None, the rest keep their positions."""
    from repro.core.boosting import GBDTConfig, SketchBoost
    from repro.data.pipeline import make_tabular
    from repro.runtime.chaos import VirtualClock
    from repro.training.serve_lib import ForestServeConfig, ForestServer

    X, y = make_tabular("multiclass", 200, 6, 4, seed=0)
    model = SketchBoost(GBDTConfig(loss="multiclass", n_trees=4, depth=3,
                                   n_bins=16, use_kernel=False)).fit(X, y)
    server = ForestServer(model.packed, model.quantizer,
                          ForestServeConfig(loss="multiclass",
                                            use_kernel=False,
                                            max_queue_rows=8),
                          clock=VirtualClock())
    res = server.serve([X[:4], X[4:10], X[10:14]])   # middle one sheds: 4+6>8
    assert res[1] is None
    assert res[0].shape == (4, 4) and res[2].shape == (4, 4)
    assert server.stats["shed_requests"] == 1
    assert server.stats["shed_rows"] == 6
    # knobs off -> exact legacy behavior, no Nones
    plain = ForestServer(model.packed, model.quantizer,
                         ForestServeConfig(loss="multiclass",
                                           use_kernel=False))
    outs = plain.serve([X[:4], X[4:10]])
    assert [o.shape[0] for o in outs] == [4, 6]
