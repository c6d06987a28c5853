"""Sketched gradient all-reduce: the paper's Random Projection operator ported
to cross-pod data parallelism (DESIGN.md §Arch-applicability — beyond-paper).

Cross-pod DP synchronizes gradients with an all-reduce whose bytes are the full
parameter count.  Here each gradient block ``g in R^{a x b}`` is compressed to
``g @ Pi`` with the JL matrix ``Pi in R^{b x k}`` (N(0, 1/k) — exactly Sec. 3.3
of the paper), psum'd over the pod axis at k/b of the bytes, and decompressed
with ``Pi^T`` (transposed-JL reconstruction).  The compression residual is kept
locally and re-injected next step (error feedback, Karimireddy et al. 2019), so
the method stays convergent.

The same key is used on every pod per step, so Pi is identical everywhere and
never communicated — the trick that makes the distributed sketch free in
`core/sketch.py` as well.

The GBDT growers (`core.tree.grow_tree`, `grow_tree_leafwise`) reduce their
histograms over the row axes through `allreduce_hist`: an exact psum, or one
whose gradient channels are sketched the same way (`sketched_hist_psum`).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

Tree = Any


def _as_matrix(g: jax.Array) -> Tuple[jax.Array, Tuple[int, ...]]:
    shape = g.shape
    if g.ndim == 0:
        return g.reshape(1, 1), shape
    if g.ndim == 1:
        return g.reshape(1, -1), shape
    return g.reshape(-1, shape[-1]), shape


def compress_block(g: jax.Array, key: jax.Array, k: int):
    """g -> (sketch (a, k), Pi) with JL Pi; skip blocks whose last dim <= k."""
    mat, shape = _as_matrix(g.astype(jnp.float32))
    a, b = mat.shape
    if b <= k:
        return mat, None, shape
    Pi = jax.random.normal(key, (b, k), jnp.float32) / jnp.sqrt(float(k))
    return mat @ Pi, Pi, shape


def decompress_block(sketch: jax.Array, Pi: Optional[jax.Array],
                     shape: Tuple[int, ...]) -> jax.Array:
    """Least-squares reconstruction: sketch @ Pi^+ = g @ (Pi (Pi^T Pi)^-1 Pi^T).

    This is the orthogonal projection of g's rows onto colspace(Pi) — a
    *contractive* compressor (E||x - C(x)||^2 = (1 - k/b) ||x||^2), which error
    feedback requires for convergence.  The naive Pi^T reconstruction is
    unbiased but its JL noise is ~ sqrt(b/k) * ||x|| > ||x||, so the feedback
    residual grows geometrically (caught by
    tests/test_runtime.py::test_sketched_psum_with_error_feedback_converges).
    The k x k solve is negligible next to the saved collective bytes.
    """
    if Pi is None:
        return sketch.reshape(shape)
    gram = Pi.T @ Pi                                     # (k, k)
    rec = jnp.linalg.solve(
        gram + 1e-6 * jnp.eye(gram.shape[0], dtype=gram.dtype),
        sketch.T).T                                      # sketch @ gram^-1
    return (rec @ Pi.T).reshape(shape)


def psum_all(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """``psum`` over each of ``axes`` in turn (no-op for ``()``)."""
    for ax in axes:
        x = jax.lax.psum(x, ax)
    return x


def sketched_hist_psum(hist: jax.Array, key: jax.Array,
                       row_axes: Sequence[str], k: int) -> jax.Array:
    """All-reduce a ``(..., c)`` histogram payload with JL-compressed
    gradient channels.

    The last axis is ``[g_1 .. g_{c-1} | count]``.  The gradient channels
    are compressed with the shared-key JL matrix ``Pi`` (replicated for
    free, same trick as `core.sketch`), psummed at ``k/(c-1)`` of the
    bytes, and reconstructed by least-squares (`decompress_block` — the
    contractive projector).  Because both the psum and the sketch are
    linear, ``psum(g @ Pi) == psum(g) @ Pi``: the reconstruction is the
    orthogonal projection of the EXACT psum onto colspace(Pi), not a noisy
    per-shard estimate.  The count channel is always exact, so split
    legality (``min_data_in_leaf``) and smaller-child choices are
    unaffected.  When ``c - 1 <= k`` the compressor is the identity and
    this is an exact psum.
    """
    c = hist.shape[-1]
    g, cnt = hist[..., :-1], hist[..., -1:]
    sk, Pi, shape = compress_block(g.reshape(-1, c - 1), key, k)
    g_hat = decompress_block(psum_all(sk, row_axes), Pi,
                             shape).reshape(g.shape)
    return jnp.concatenate([g_hat, psum_all(cnt, row_axes)], axis=-1)


def allreduce_hist(hist: jax.Array, row_axes: Sequence[str], *,
              compression: str = "none", k: int = 0,
              key: Optional[jax.Array] = None) -> jax.Array:
    """The growers' histogram all-reduce over the row axes: exact, or
    through `sketched_hist_psum` under ``compression="sketch"``.  With no
    row axes (one device) the histogram is returned as it is."""
    if not row_axes:
        return hist
    if compression == "sketch":
        return sketched_hist_psum(hist, key, row_axes, k)
    return psum_all(hist, row_axes)


def sketched_psum(grads: Tree, key: jax.Array, axis_name: str, *, k: int = 32,
                  residuals: Optional[Tree] = None) -> Tuple[Tree, Tree]:
    """All-reduce `grads` over ``axis_name`` through a JL sketch.

    For use *inside shard_map / pmapped code*.  Returns (mean-reduced grads,
    new error-feedback residuals).  Communication volume per block drops from
    a*b to a*k floats.
    """
    flat, treedef = jax.tree.flatten(grads)
    res_flat = (jax.tree.leaves(residuals) if residuals is not None
                else [jnp.zeros_like(g, dtype=jnp.float32) for g in flat])
    keys = jax.random.split(key, len(flat))
    out, new_res = [], []
    n = jax.lax.psum(1, axis_name)
    for g, r, kk in zip(flat, res_flat, keys):
        corrected = g.astype(jnp.float32) + r
        sk, Pi, shape = compress_block(corrected, kk, k)
        sk = jax.lax.psum(sk, axis_name) / n
        approx = decompress_block(sk, Pi, shape)
        new_res.append((corrected - approx))       # local error feedback
        out.append(approx.astype(g.dtype))
    return (jax.tree.unflatten(treedef, out),
            jax.tree.unflatten(treedef, new_res))


def exact_psum(grads: Tree, axis_name: str) -> Tree:
    n = jax.lax.psum(1, axis_name)
    return jax.tree.map(lambda g: jax.lax.psum(g, axis_name) / n, grads)


def compression_ratio(grads: Tree, k: int) -> float:
    """Achieved bytes ratio of sketched vs exact all-reduce."""
    full = sketched = 0
    for g in jax.tree.leaves(grads):
        mat, _ = _as_matrix(g)
        a, b = mat.shape
        full += a * b
        sketched += a * min(b, k) if b > k else a * b
    return sketched / max(full, 1)
