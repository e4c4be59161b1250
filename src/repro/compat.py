"""The repo's one spelling of a device mesh and of ``shard_map``.

Import ``make_mesh`` / ``shard_map`` from here instead of from ``jax``
directly: meshes carry explicit ``Auto`` axis types, and ``shard_map``
always runs with the replication checker off (``check_vma=False``) —
the CAMR collective bodies call Pallas kernels, which have no
replication rule.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "shard_map"]


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication checker off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
