"""Production mesh construction.

Axes:
  pod    — cross-pod data parallelism (gradient reduction over DCI/ICI);
  data   — in-pod data parallelism + FSDP weight sharding;
  model  — tensor / expert / sequence(-cache) parallelism.

Importing this module never touches jax device state; call the function.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Arbitrary mesh for tests/smoke (e.g. (1, 1) on one CPU device)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape))


def dp_axes(mesh) -> tuple:
    """The data-parallel axes of a mesh (pod composes with data)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def axis_size(mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1
