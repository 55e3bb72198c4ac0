"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then builds meshes.

Topology (TPU v5e pods):
  single-pod:  (16, 16)    axes ("data", "model")      256 chips
  multi-pod:   (2, 16, 16) axes ("pod", "data", "model")  512 chips
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_local_mesh(data: int = 1, model: int = 1) -> Mesh:
    """Small mesh over however many (host) devices exist — smoke tests and
    the subprocess multi-device tests."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
