"""Production meshes.  Functions, not module constants — importing this module
never touches jax device state (required by the dry-run contract)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """Arbitrary mesh (tests / elastic re-mesh use small shapes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def device_subset_mesh(n_devices: int, model_parallel: int = 1,
                       axes: Tuple[str, str] = ("data", "model")):
    """(data, model) mesh over the FIRST ``n_devices`` devices.

    Unlike `make_mesh` (which wants the full device count), this builds a
    mesh over any prefix of the process's devices — the device-count
    scaling axis of the distributed GBDT bench and the emulated-host parity
    suite both sweep it.
    """
    import numpy as np
    devs = jax.devices()[:n_devices]
    if len(devs) < n_devices:
        raise ValueError(f"need {n_devices} devices, have {len(devs)}; "
                         "set XLA_FLAGS=--xla_force_host_platform_device_"
                         f"count={n_devices} (before jax is imported) to "
                         "emulate them on CPU")
    if n_devices % model_parallel:
        raise ValueError(f"n_devices={n_devices} not divisible by "
                         f"model_parallel={model_parallel}")
    arr = np.asarray(devs).reshape(n_devices // model_parallel,
                                   model_parallel)
    return jax.sharding.Mesh(arr, axes)


def host_device_mesh(model_parallel: int = 1, pods: int = 1):
    """Best-effort mesh over whatever devices exist (CPU smoke runs)."""
    n = len(jax.devices())
    mp = model_parallel if n % model_parallel == 0 else 1
    dp = n // mp // pods
    if pods > 1:
        return make_mesh((pods, dp, mp), ("pod", "data", "model"))
    return make_mesh((dp, mp), ("data", "model"))
