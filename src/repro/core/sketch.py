"""Sketched split scoring — the paper's core contribution (Section 3 + Appendix A).

The split search scores candidate partitions with eq. (4),
``S(R) = ||sum_{i in R} g_i||^2 / (|R| + lambda)``, whose cost scales with the
width of the gradient matrix.  Each sketch replaces the ``(n, d)`` gradients
``G`` with a ``(n, k)`` surrogate ``G_k`` for the *search only* — leaf values
(eq. (3)) always use the full gradients, which is why packed leaf blocks stay
width ``d`` while the split statistics are width ``k`` (see `core.forest`).

All four sketches are expressed as a column operator ``G_k = G @ Pi`` so that on a
``(pod, data, model)`` mesh with ``G`` sharded (rows -> data, outputs -> model) the
sketch is a *local matmul + psum over the model axis*.  This is the TPU-native form:
the MXU does the contraction and the collective collapses the output-parallel axis,
leaving a small replicated ``(n_local, k)`` matrix for the split search.

Methods
-------
=====================  ===========  ==============================  ===========
``sketch_method``      Paper        Operator ``Pi`` (d, k)          Extra cost
=====================  ===========  ==============================  ===========
``top_outputs``        Sec. 3.1     one-hot of top-k column norms   O(n d)
                                    (`top_outputs_selector`)
``random_sampling``    Sec. 3.2     importance-sampled one-hot,     O(n d)
                                    scaled 1/sqrt(k p_i) for
                                    unbiasedness
                                    (`random_sampling_selector`)
``random_projection``  Sec. 3.3     JL Gaussian, i.i.d. N(0, 1/k)   O(n d k)
                                    — the paper's recommended
                                    default
                                    (`random_projection_matrix`)
``truncated_svd``      App. A.1     top-k right singular subspace   O(n d^2
                                    via the d x d Gram eigh          + d^3)
                                    (`truncated_svd_projector`)
``none``               —            identity: SketchBoost Full      0
                                    baseline (also when k >= d)
=====================  ===========  ==============================  ===========

Entry points: `build_sketch` (single device) and `sketch_sharded` (inside
shard_map); both are consumed by `boosting._boost_round`, which concatenates
the sketch with the SGB/GOSS weight channel into the split statistics.

Precision: the paper's sketch is a float32 operator, and on a TPU a float32
matrix product at the default precision is one bfloat16 pass (about 2^-9
relative), which rounds the split statistics, and so every split gain, as
coarsely as bfloat16 histograms would.  Every contraction here therefore
runs at ``Precision.HIGHEST``; the CPU ignores the flag.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST    # fp32 sketch: no single bf16 MXU pass

SKETCH_METHODS = ("none", "top_outputs", "random_sampling", "random_projection",
                  "truncated_svd")


def column_sq_norms(G: jax.Array, *, axis_name: Optional[str] = None) -> jax.Array:
    """Squared column norms ``||g_j||^2`` of G, reduced over the row axis.

    Under shard_map with rows sharded over ``axis_name``, psums the partial norms so
    every shard sees the global norms (outputs stay sharded over the model axis).
    """
    norms = jnp.sum(jnp.square(G.astype(jnp.float32)), axis=0)
    if axis_name is not None:
        norms = jax.lax.psum(norms, axis_name)
    return norms


# ---------------------------------------------------------------------------
# Selector-matrix constructions.  Each returns Pi with shape (d, k) so the
# sketch itself is always `G @ Pi` (optionally followed by a model-axis psum
# when the d axis is sharded — see `sketch_sharded`).
# ---------------------------------------------------------------------------

def top_outputs_selector(norms: jax.Array, k: int) -> jax.Array:
    """One-hot selector of the k columns with the largest norm."""
    d = norms.shape[0]
    _, idx = jax.lax.top_k(norms, k)                       # (k,)
    return jax.nn.one_hot(idx, d, dtype=jnp.float32).T     # (d, k)


def random_sampling_selector(norms: jax.Array, k: int, key: jax.Array) -> jax.Array:
    """Importance-sampled selector with unbiasedness scaling 1/sqrt(k p_i).

    p_i = ||g_i||^2 / sum_j ||g_j||^2 (variance-optimal, Sec. 3.2).  Indices are
    drawn i.i.d. with replacement, matching the paper.
    """
    d = norms.shape[0]
    total = jnp.sum(norms)
    # Guard the all-zero-gradient corner (fully fit model): fall back to uniform.
    safe = total > 0
    p = jnp.where(safe, norms / jnp.maximum(total, 1e-30), jnp.full_like(norms, 1.0 / d))
    logits = jnp.log(jnp.maximum(p, 1e-30))
    idx = jax.random.categorical(key, logits, shape=(k,))  # (k,) iid
    scale = 1.0 / jnp.sqrt(k * jnp.maximum(p[idx], 1e-30)) # (k,)
    return jax.nn.one_hot(idx, d, dtype=jnp.float32).T * scale[None, :]


def random_projection_matrix(d: int, k: int, key: jax.Array) -> jax.Array:
    """JL projection: i.i.d. N(0, 1/k) entries (Sec. 3.3)."""
    return jax.random.normal(key, (d, k), dtype=jnp.float32) / jnp.sqrt(float(k))


def truncated_svd_projector(G: jax.Array, k: int) -> jax.Array:
    """Top-k right singular subspace of G via eigh of the d x d Gram matrix.

    ``G @ V_k`` equals ``U_k @ Sigma_k`` (the appendix's truncated-SVD sketch) up to
    column signs, which the scoring function is invariant to.  O(n d^2 + d^3); the
    appendix flags this cost — provided as the quality-upper-bound baseline.
    """
    Gf = G.astype(jnp.float32)
    gram = jnp.dot(Gf.T, Gf, precision=_HI)                 # (d, d)
    _, vecs = jnp.linalg.eigh(gram)                         # ascending eigenvalues
    return vecs[:, -k:]                                     # (d, k)


# ---------------------------------------------------------------------------
# Public entry points.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("method", "k"))
def build_sketch(G: jax.Array, *, method: str, k: int,
                 key: Optional[jax.Array] = None) -> jax.Array:
    """Single-device sketch ``G_k`` of the gradient matrix ``G`` (n, d) -> (n, k).

    ``method='none'`` or ``k >= d`` returns G unchanged (SketchBoost Full).
    """
    n, d = G.shape
    if method == "none" or k >= d:
        return G.astype(jnp.float32)
    if method in ("top_outputs", "random_sampling"):
        norms = column_sq_norms(G)
        if method == "top_outputs":
            Pi = top_outputs_selector(norms, k)
        else:
            if key is None:
                raise ValueError("random_sampling requires a PRNG key")
            Pi = random_sampling_selector(norms, k, key)
    elif method == "random_projection":
        if key is None:
            raise ValueError("random_projection requires a PRNG key")
        Pi = random_projection_matrix(d, k, key)
    elif method == "truncated_svd":
        Pi = truncated_svd_projector(G, k)
    else:
        raise ValueError(f"unknown sketch method {method!r}")
    return jnp.dot(G.astype(jnp.float32), Pi, precision=_HI)


def sketch_sharded(G_local: jax.Array, *, method: str, k: int,
                   key: Optional[jax.Array] = None,
                   d_global: Optional[int] = None,
                   model_axis: str = "model",
                   data_axes=("data",),
                   shard_index: Optional[jax.Array] = None) -> jax.Array:
    """Distributed sketch for use *inside shard_map*.

    ``G_local`` is the (n_local, d_local) block of G with rows sharded over
    ``data_axes`` and outputs sharded over ``model_axis``.  Every method reduces to
    ``psum_model(G_local @ Pi_local)`` where ``Pi_local`` is this shard's (d_local, k)
    slice of the global (d, k) operator:

    * top_outputs / random_sampling: column norms are psum'd over the data axes and
      all-gathered over the model axis so every shard sees the global (d,) norms; the
      global selector is built identically on every shard (same key), then sliced.
    * random_projection: the global Gaussian Pi is generated from the *same* key on
      every shard and sliced — no communication for Pi at all.

    Returns the replicated-over-model (n_local, k) sketch.
    """
    n_loc, d_loc = G_local.shape
    if d_global is None:
        d_global = d_loc * jax.lax.psum(1, model_axis)
    if method == "none" or k >= d_global:
        # Full baseline: gather the output axis so split search sees all d columns.
        out = jax.lax.all_gather(G_local.astype(jnp.float32), model_axis, axis=1,
                                 tiled=True)
        return out
    if shard_index is None:
        shard_index = jax.lax.axis_index(model_axis)
    Gf = G_local.astype(jnp.float32)
    if method in ("top_outputs", "random_sampling"):
        local_norms = jnp.sum(jnp.square(Gf), axis=0)
        for ax in data_axes:
            local_norms = jax.lax.psum(local_norms, ax)
        norms = jax.lax.all_gather(local_norms, model_axis, axis=0, tiled=True)  # (d,)
        if method == "top_outputs":
            Pi = top_outputs_selector(norms, k)
        else:
            Pi = random_sampling_selector(norms, k, key)
    elif method == "random_projection":
        Pi = random_projection_matrix(d_global, k, key)
    elif method == "truncated_svd":
        # The appendix baseline, distributed: gather the (small) output axis,
        # psum the d x d Gram over the row axes, and eigh it replicated on
        # every shard — O(d^2 n_loc + d^3) per shard, same asymptotics the
        # appendix flags for the single-device baseline.  Gain scores are
        # invariant to the column signs eigh leaves unspecified, so the
        # split search is well-defined even where eigenvectors are sign-
        # ambiguous across runs.
        G_full = jax.lax.all_gather(Gf, model_axis, axis=1, tiled=True)
        gram = jnp.dot(G_full.T, G_full, precision=_HI)     # (d, d) local part
        for ax in data_axes:
            gram = jax.lax.psum(gram, ax)
        _, vecs = jnp.linalg.eigh(gram)
        Pi = vecs[:, -k:]
    else:
        raise ValueError(f"unknown sketch method {method!r}")
    Pi_local = jax.lax.dynamic_slice_in_dim(Pi, shard_index * d_loc, d_loc, axis=0)
    return jax.lax.psum(jnp.dot(Gf, Pi_local, precision=_HI), model_axis)
