"""The two mesh spellings every distributed path in the repo goes through:
``jax.shard_map`` with the replication check off by default, and
``jax.make_mesh`` with Auto axis types (jax >= 0.9)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def shard_map(f, *, mesh, in_specs, out_specs, check_vma: bool = False):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


def make_mesh(axis_shapes, axis_names):
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))
