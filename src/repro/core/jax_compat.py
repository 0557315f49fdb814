"""Mesh and ``shard_map`` helpers shared by the distributed screening and
solving backends.

    shard_map(...)   ``jax.shard_map`` with the replication check off
    make_mesh(...)   ``jax.make_mesh`` with Auto axis types
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with ``check_vma=False`` (the label-prop
    while_loop's outputs are not provably replicated)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis of type Auto."""
    return jax.make_mesh(
        axis_shapes,
        axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


_LOCAL_MESHES: dict[tuple, object] = {}


def local_device_mesh(axis: str = "data"):
    """1-D mesh over every local device (the engine's default placement).

    Cached per (axis, device count): the device set is fixed for a process
    lifetime, and re-building the mesh per call both wastes time (tests that
    emulate 8 host devices re-init it hundreds of times) and defeats any
    compiled-function cache keyed on mesh identity."""
    key = (axis, jax.device_count())
    mesh = _LOCAL_MESHES.get(key)
    if mesh is None:
        mesh = make_mesh((jax.device_count(),), (axis,))
        _LOCAL_MESHES[key] = mesh
    return mesh
