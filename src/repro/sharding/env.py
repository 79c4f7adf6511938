"""Mesh helpers shared by the distributed partitioner and runtime."""
from __future__ import annotations

from jax.sharding import AxisType, Mesh


def auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with every axis ``Auto``.

    ``jax.make_mesh`` makes ``Explicit`` axes by default, which put the
    sharding into array types: a sharded result then fails later
    unsharded indexing, and ``with_sharding_constraint`` refuses the
    axes.  Code here shards through specs and constraints, so it runs on
    ``Auto`` meshes whatever the caller built.
    """
    if all(t == AxisType.Auto for t in mesh.axis_types):
        return mesh
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))
