"""Production meshes: 16x16 (one v5e pod, 256 chips) and 2x16x16 (two pods).

Defined as functions (not module constants) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""

from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = math.prod(shape)
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(
            f"mesh {shape} needs {need} devices, have {len(devices)} "
            f"(dry-run sets --xla_force_host_platform_device_count=512)")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices[:need])


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests)."""
    need = data * model
    devices = jax.devices()
    if len(devices) < need:
        raise RuntimeError(f"need {need} devices, have {len(devices)}")
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2,
                         devices=devices[:need])
