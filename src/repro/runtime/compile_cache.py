"""Where JAX's persistent compilation cache lives.

Every entry point that compiles real work (`chip_smoke.py`, the chip
benchmark in `bench/`, `launch/train.py`, `launch/serve.py`) calls
`enable_compile_cache` before it touches a device.  The cache key includes
the directory, so the directory must not move between runs: it is either
the one ``JAX_COMPILATION_CACHE_DIR`` names or a fixed, git-ignored
``.jax_cache/`` at the checkout root.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable itself
    and nothing is set here.  Otherwise ``jax_compilation_cache_dir`` is
    pointed at `DEFAULT_CACHE_DIR`, so a second run in the same checkout
    loads its executables instead of compiling them again.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
