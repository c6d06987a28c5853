"""Faults planted under the timed path, to show that the check sees them.

Each fault replaces one function of the program for the duration of a
``with planted(name):`` block (and clears JAX's caches on the way in and
out, so the compiled programs are traced again with the fault in them):

* ``state_unchanged``: a boosting round returns the scores it was given,
  so every later tree is grown on stale gradients;
* ``half_batch``: half of the rows get weight 0, so the histograms and
  leaf sums of every tree see only the other half;
* ``answer_altered``: every tree's leaf values are scaled by 1.001 where
  the grower produces them.
"""
from __future__ import annotations

import contextlib

TRAIN_FAULTS = ("state_unchanged", "half_batch", "answer_altered")


def _patch(name: str):
    import jax.numpy as jnp
    from repro.core import boosting as B
    from repro.core import tree as T
    if name == "state_unchanged":
        orig = B._boost_round

        def round_(F, codes, Y, key, cfg):
            _, tree = orig(F, codes, Y, key, cfg)
            return F, tree
        return B, "_boost_round", round_
    if name == "half_batch":
        orig = B._sample_weights

        def weights(key, G, cfg):
            n = G.shape[0]
            return orig(key, G, cfg) * (jnp.arange(n) < n // 2)[:, None]
        return B, "_sample_weights", weights
    if name == "answer_altered":
        orig = T.grow_tree

        def grow(*a, **kw):
            tree, pos = orig(*a, **kw)
            return tree._replace(value=tree.value * 1.001), pos
        return T, "grow_tree", grow
    raise ValueError(f"unknown fault {name!r}")


@contextlib.contextmanager
def planted(name: str):
    import jax
    owner, attr, fn = _patch(name)
    orig = getattr(owner, attr)
    jax.clear_caches()
    setattr(owner, attr, fn)
    try:
        yield
    finally:
        setattr(owner, attr, orig)
        jax.clear_caches()
