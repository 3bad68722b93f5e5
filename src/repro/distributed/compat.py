"""Mesh and shard_map spellings shared by the distributed substrate.

Every caller builds meshes through ``make_mesh`` so all of them get the
same Auto axis types (sharding propagated by the compiler, not pinned by
the type system); the rest are the JAX 0.9 APIs under the names the
callers import.
"""
from __future__ import annotations

import jax
from jax.sharding import AbstractMesh, AxisType

shard_map = jax.shard_map
set_mesh = jax.set_mesh
axis_size = jax.lax.axis_size


def abstract_mesh(axis_shapes, axis_names) -> AbstractMesh:
    """A device-free mesh of the given axis sizes and names."""
    return AbstractMesh(tuple(axis_shapes), tuple(axis_names))


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """jax.make_mesh with Auto axis types."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names), **kw)
