"""Thin wrappers over the JAX mesh and ``shard_map`` API.

Callers pass the replication-check flag as ``check_replication`` and get
meshes whose axes are all ``Auto``, so sharding stays propagated by the
compiler unless a ``with_sharding_constraint`` says otherwise.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs, check_replication: bool = True):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                         check_vma=check_replication)


def make_mesh(axis_shapes, axis_names):
    """Device mesh with ``Auto`` axis types."""
    axis_types = (jax.sharding.AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types)


def set_mesh(mesh):
    """Ambient-mesh context manager."""
    return jax.set_mesh(mesh)


def get_ambient_mesh():
    """The abstract mesh set by :func:`set_mesh`; its ``axis_names`` are
    empty when no mesh is set."""
    return jax.sharding.get_abstract_mesh()
