"""Production mesh construction (multi-pod dry-run contract).

A function, not a module-level constant — importing this module never
touches jax device state.  Single pod: (data=16, model=16) = 256 chips;
multi-pod adds a leading pod axis: (pod=2, data=16, model=16) = 512 chips.
"""
from __future__ import annotations

import jax
import numpy as np


__all__ = ["make_production_mesh", "make_mesh_for"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    ndev = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) < ndev:
        raise RuntimeError(
            f"need {ndev} devices for mesh {shape}, have {len(devices)} — "
            "the dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512")
    # jax.make_mesh uses all devices by default; slice when we have extras
    # (the dry-run process exposes 512 but the single-pod mesh needs 256).
    return jax.make_mesh(shape, axes, devices=devices[:ndev],
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_mesh_for(n_devices: int, *, model_parallel: int = 1):
    """Small-scale mesh for tests/examples: (data, model) over what exists."""
    devices = jax.devices()[:n_devices]
    data = n_devices // model_parallel
    return jax.make_mesh((data, model_parallel), ("data", "model"),
                         devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
