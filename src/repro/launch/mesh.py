"""Production mesh construction.

Defined as functions (not module-level constants) so importing this
module never touches jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; everything else (tests, benches) sees the real single CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e pod: 16x16 = 256 chips; multi-pod: 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(*, model: int = 1, data: int = 1):
    """Small mesh over forced host devices (tests use 8)."""
    return _make_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Axes over which the global batch is sharded."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)
