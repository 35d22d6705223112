"""The one spelling of the ``shard_map``/mesh API family this repo uses.

The repo pins jax 0.9 (``requirements.txt``): ``jax.shard_map`` with
``check_vma``, meshes with explicit ``AxisType.Auto`` axes and
``jax.lax.axis_size``.  Every module that builds a mesh or a shard_map goes
through these helpers, so the conventions (replication checking off, Auto
axes) live in one place.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled."""
    return jax.shard_map(
        f, mesh=mesh, check_vma=False, in_specs=in_specs, out_specs=out_specs
    )


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(
        axis_shapes,
        axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


axis_size = jax.lax.axis_size


def make_mesh_from_devices(dev_array, axis_names):
    """``jax.sharding.Mesh`` over an explicit device array, Auto axes."""
    return jax.sharding.Mesh(
        dev_array,
        axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )
