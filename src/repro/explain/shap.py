"""Multioutput SHAP values over a `PackedForest`.

Two exact algorithms, one dispatch surface:

  * ``path_dependent`` (default) — Lundberg-style TreeSHAP using the packed
    per-node covers as the background distribution.  Runs under the same
    ``use_kernel`` modes as prediction: the Pallas path-walk kernel
    (`kernels.shap_kernel`) on TPU / interpret, the jnp oracle
    (`kernels.ref.tree_shap_ref`) otherwise — bit-identical by construction.
  * ``interventional`` — exact interventional TreeSHAP against an explicit
    background dataset (`kernels.ref.tree_shap_interventional_ref`);
    attributions average over background rows, so the matching base value is
    the mean background prediction.

Both satisfy local accuracy per tree and per path:
``base_values + phi.sum(feature_axis) == predict_raw`` up to float32
accumulation order.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import forest as FO
from repro.core import histogram as H
from repro.explain.paths import PathPack, build_path_pack
from repro.kernels import ref

ALGORITHMS = ("path_dependent", "interventional")


def expected_values(pf, pack: Optional[PathPack] = None) -> jax.Array:
    """Path-dependent expected prediction ``E[F]`` as a ``(d,)`` vector.

    ``base + lr * sum_t sum_leaves leaf_weight * leaf_value`` with each
    tree's contribution placed at its output column — the ``base_values``
    that pair with path-dependent SHAP.
    """
    pack = build_path_pack(pf) if pack is None else pack
    # HIGHEST: a TPU's default f32 matmul is one bf16 pass, which moves the
    # base value by ~1e-3 relative and breaks local accuracy.
    e_tree = jnp.einsum("tl,tlw->tw", pack.leaf_weight, pack.leaf,
                        precision=jax.lax.Precision.HIGHEST)        # (T, w)
    if pf.leaf_width == pf.n_outputs:
        return pf.base + pf.lr * jnp.sum(e_tree, axis=0)
    scat = jax.ops.segment_sum(e_tree[:, 0], pf.out_col.astype(jnp.int32),
                               num_segments=pf.n_outputs)
    return pf.base + pf.lr * scat


def _phi_path_dependent(pf, pack: PathPack, codes: jax.Array,
                        mode: str) -> jax.Array:
    n, m = codes.shape
    d = pf.n_outputs
    from repro.kernels import ops as kops
    mode, interp = kops.resolve_dispatch(mode)
    if mode != "jnp":
        return kops.tree_shap(codes, pack.slot_feat, pack.slot_lo,
                              pack.slot_hi, pack.slot_z, pack.leaf,
                              pf.out_col, pf.lr, n_outputs=d, depth=pf.depth,
                              interpret=interp)
    phi0 = jnp.zeros((n, m, d), jnp.float32)
    return ref.tree_shap_ref(phi0, codes, pack.slot_feat, pack.slot_lo,
                             pack.slot_hi, pack.slot_z, pack.leaf,
                             pf.out_col, pf.lr, depth=pf.depth)


def _phi_interventional(pf, pack: PathPack, codes: jax.Array,
                        bg_codes: jax.Array) -> jax.Array:
    n, m = codes.shape
    phi0 = jnp.zeros((n, m, pf.n_outputs), jnp.float32)
    return ref.tree_shap_interventional_ref(
        phi0, codes, bg_codes, pack.slot_feat, pack.slot_lo, pack.slot_hi,
        pack.leaf, pf.out_col, pf.lr, depth=pf.depth)


def shap_values(pf, codes: jax.Array, *, algorithm: str = "path_dependent",
                background: Optional[jax.Array] = None, mode="jnp",
                row_chunk: int = 0,
                pack: Optional[PathPack] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """SHAP attributions for all outputs at once.

    Args:
      pf:        `core.forest.PackedForest` (cover-carrying for
                 ``path_dependent``).
      codes:     (n, m) binned features (`Quantizer` output).
      algorithm: "path_dependent" | "interventional".
      background: (B, m) binned background rows (interventional only).
      mode:      ``use_kernel`` request, resolved like `forest.forest_apply`.
      row_chunk: rows per dispatch (0 = all); the tail is zero-padded so a
                 single compiled executable serves every chunk.
      pack:      optional pre-built `PathPack` (e.g. a server's cache).
    Returns:
      ``(phi, base_values)`` — (n, m, d) float32 attributions and the (d,)
      expected value they are measured against.
    """
    if algorithm not in ALGORITHMS:
        raise ValueError(f"unknown SHAP algorithm {algorithm!r}; "
                         f"expected one of {ALGORITHMS}")
    mode = H.resolve_kernel_mode(mode)
    if pack is None:
        pack = build_path_pack(pf,
                               need_cover=(algorithm == "path_dependent"))
    if algorithm == "interventional":
        if background is None:
            raise ValueError("interventional SHAP needs a background "
                             "dataset (binned codes)")
        base = jnp.mean(FO.predict_raw(pf, background, mode=mode), axis=0)

        def run(part):
            return _phi_interventional(pf, pack, part, background)
    else:
        base = expected_values(pf, pack)

        def run(part):
            return _phi_path_dependent(pf, pack, part, mode)

    n = codes.shape[0]
    chunk = n if row_chunk <= 0 else min(row_chunk, n)
    outs = []
    for s in range(0, n, chunk):
        part = codes[s:s + chunk]
        if part.shape[0] < chunk:                 # pad tail, keep one trace
            part = jnp.pad(part, ((0, chunk - part.shape[0]), (0, 0)))
        outs.append(run(part))
    phi = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
    return phi[:n], base
