"""Production mesh builders (assignment-mandated signatures).

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state. The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; smoke tests and benches see the real single device.
"""

from __future__ import annotations

from typing import Tuple

import jax

__all__ = ["make_production_mesh", "make_mesh", "single_device_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips, axes (data, model).
    Multi-pod: (2, 16, 16) = 512 chips, axes (pod, data, model)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...], devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD partitioning);
    ``devices`` picks the devices (default: all of them)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices)


def single_device_mesh(axes: Tuple[str, ...] = ("data", "model")):
    """1-device mesh with the production axis names — lets CPU tests run
    the exact production code path (shard_map, constraints) unchanged."""
    return make_mesh((1,) * len(axes), axes)
