"""Device meshes for the row-sharded query engine.

The mesh is ``(data, 1)`` over ``("data", "model")``: shards live on the
``data`` axis, and the size-1 ``model`` axis keeps the ``shard_map`` specs
of :mod:`repro.dist.query` as they are.

Defined as functions so importing this module never touches jax device
state (a caller may still set XLA_FLAGS before jax initialises).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_host_mesh(data: int | None = None) -> Mesh:
    """A ``(data, 1)`` mesh over the first ``data`` devices (all by default)."""
    if data is None:
        data = len(jax.devices())
    return jax.make_mesh((data, 1), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))
