"""Every main-path Pallas kernel compiles for a TPU v5e chip.

Compiles (does not run) each GBDT kernel wrapper with ``interpret=False``
for a *described* v5e chip at the widths of the shipped configuration
(`repro.configs.sketchboost_tabular`: m=100 features, d=512 outputs,
k=5 sketch -> c=6 histogram channels, 256 bins, depth 6), so Mosaic's
block-shape, layout and VMEM rules are enforced on every CPU test run.
Row counts are small: they do not change what the compiler checks.

The topology is described inside module fixtures, never at import: only
the test process that runs this file may load the TPU compiler.
"""
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

M, C, B, D, DEPTH = 100, 6, 256, 512, 6
N_ROWS, N_TREES = 8192, 8
N_NODES = 2 ** (DEPTH + 1) - 1 + 1          # depth-6 pointer forest, padded
N_LEAVES = 2 ** DEPTH


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                           # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # Executables for a described chip can be written to the persistent
    # cache but never read back without one: keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype,
                                                    sharding=one_chip)


def assert_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("hist_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n_nodes", [2, 32])
def test_histogram_splits_level_compiles(shape, hist_dtype, n_nodes):
    def level(codes, stats, order, counts, prev, lam, min_data):
        return ops.histogram_splits_level(
            codes, stats, order, counts, prev, lam, min_data,
            n_nodes=n_nodes, n_bins=B, subtract=True, hist_dtype=hist_dtype,
            interpret=False)

    assert_compiles(level, shape((N_ROWS, M), jnp.uint8),
                    shape((N_ROWS, C), jnp.float32),
                    shape((N_ROWS,), jnp.int32), shape((n_nodes,), jnp.int32),
                    shape((M, n_nodes // 2 * B, 128), jnp.float32),
                    shape((), jnp.float32), shape((), jnp.float32))


@pytest.mark.parametrize("m,c", [(93, 10), (60, 6)])
@pytest.mark.parametrize("n_nodes", [1, 32])
def test_histogram_splits_level_otto_widths_sums_in_kernel(shape, n_nodes,
                                                           m, c):
    """At otto's widths (93 features, 9 classes + count = 10 channels ->
    128 lanes, 256 bins, 256-row tiles) and Dionis's (60 features, k = 5 +
    count = 6 channels) the tiles kernel's scalar-prefetched tile->node
    output map is Mosaic-legal, it reads the stats as one bf16 operand of
    128 lanes (three exact parts of each channel) and writes float32 node
    histograms, and the level program sums tiles into nodes in the kernel:
    no scatter is left in it, not even as the source op of a fusion (op
    names are kept in the compiled metadata)."""

    def level(codes, stats, order, counts, prev, lam, min_data):
        return ops.histogram_splits_level(
            codes, stats, order, counts, prev, lam, min_data,
            n_nodes=n_nodes, n_bins=B, subtract=n_nodes > 1, row_tile=256,
            interpret=False)

    prev = (None if n_nodes == 1
            else shape((m, n_nodes // 2 * B, 128), jnp.float32))
    text = assert_compiles(level, shape((N_ROWS, m), jnp.uint8),
                           shape((N_ROWS, c), jnp.float32),
                           shape((N_ROWS,), jnp.int32),
                           shape((n_nodes,), jnp.int32), prev,
                           shape((), jnp.float32), shape((), jnp.float32))
    assert "scatter" not in text
    call, = [line for line in text.splitlines()
             if "%hist_tiles_pallas" in line and "custom-call(" in line]
    assert f"= f32[{m},{n_nodes},256,128]" in call
    operands = call.split("operand_layout_constraints={")[1].split("}")
    assert re.match(r"bf16\[\d+,128\]", operands[2].lstrip(", ")), call


def test_node_histogram_compiles(shape):
    assert_compiles(
        lambda cg, sg: ops.node_histogram(cg, sg, n_bins=B, interpret=False),
        shape((N_ROWS, M), jnp.uint8), shape((N_ROWS, C), jnp.float32))


def test_split_scan_compiles(shape):
    assert_compiles(
        lambda h, lam, md: ops.split_scan(h, lam, md, n_nodes=32, n_bins=B,
                                          interpret=False),
        shape((M, 32 * B, C), jnp.float32), shape((), jnp.float32),
        shape((), jnp.float32))


def _forest_shapes(shape, thr_dtype, leaf_dtype):
    node = shape((N_TREES, N_NODES), jnp.int32)
    return (shape((N_ROWS, D), jnp.float32), shape((N_ROWS, M), jnp.uint8),
            node, shape((N_TREES, N_NODES), thr_dtype), node, node,
            shape((N_TREES, N_NODES, D), leaf_dtype))


def test_forest_apply_compiles(shape):
    def apply(F, codes, feat, thr, left, right, leaf, out_col):
        return ops.forest_apply(F, codes, feat, thr, left, right, leaf,
                                out_col, 0.05, depth=DEPTH, interpret=False)

    assert_compiles(apply,
                    *_forest_shapes(shape, jnp.int32, jnp.float32),
                    shape((N_TREES,), jnp.int32))


@pytest.mark.parametrize("leaf_dtype", [jnp.int8, jnp.bfloat16])
def test_forest_apply_quant_compiles(shape, leaf_dtype):
    def apply(F, codes, feat, thr, left, right, leaf, scale, out_col):
        return ops.forest_apply_quant(F, codes, feat, thr, left, right, leaf,
                                      scale, out_col, 0.05, depth=DEPTH,
                                      interpret=False)

    assert_compiles(apply,
                    *_forest_shapes(shape, jnp.uint8, leaf_dtype),
                    shape((N_TREES, 1), jnp.float32),
                    shape((N_TREES,), jnp.int32))


def test_tree_shap_compiles(shape):
    def shap(codes, sf, lo, hi, z, leaf, out_col):
        return ops.tree_shap(codes, sf, lo, hi, z, leaf, out_col, 0.05,
                             n_outputs=D, depth=DEPTH, interpret=False)

    slot = shape((N_TREES, N_LEAVES, DEPTH), jnp.int32)
    assert_compiles(shap, shape((64, M), jnp.uint8), slot, slot, slot,
                    shape((N_TREES, N_LEAVES, DEPTH), jnp.float32),
                    shape((N_TREES, N_LEAVES, D), jnp.float32),
                    shape((N_TREES,), jnp.int32))
