"""Production meshes.

Single pod: (data=16, model=16) = 256 chips (one v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; ``pod`` is an outer
data-parallel axis by default (gradients all-reduce over pod x data) and
can alternatively run as 2 pipeline stages (dist/pipeline.py).

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialisation).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return _make_mesh((data, model), ("data", "model"))


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
