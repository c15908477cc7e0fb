"""Thin wrappers over the jax calls whose spelling the repo pins in one place.

The framework targets jax 0.9: ``jax.shard_map`` with replication checking
off and ``jax.make_mesh(..., axis_types=...)`` with Auto axes.  Every call
site routes through these wrappers so the arguments are chosen once.
"""

from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def get_ambient_mesh():
    """The mesh installed by ``jax.set_mesh``, or ``None`` outside one."""
    m = jax.sharding.get_abstract_mesh()
    return m if (m is not None and m.shape) else None


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with Auto axis types."""
    kw = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        axis_shapes,
        axis_names,
        axis_types=tuple(jax.sharding.AxisType.Auto for _ in axis_names),
        **kw,
    )
