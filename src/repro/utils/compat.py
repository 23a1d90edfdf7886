"""The one spelling of mesh and shard_map construction the repo uses.

Every mesh/shard_map construction routes through here, so the options
the whole stack relies on (replication checking off, Auto axis types)
are set in one place.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(fn, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))
