"""One-line wrappers over jax's sharding API, as the repo uses it.

Every mesh is built with Auto axis types, and every ``shard_map`` turns
replication checking off (the repo's collectives are explicit).

Usage:
    from repro.core import jax_compat as jc
    mesh = jc.make_mesh((2, 4), ("data", "model"))
    fn = jc.shard_map(f, mesh=mesh, in_specs=..., out_specs=...)
    with jc.set_mesh(mesh):
        ...
"""
from __future__ import annotations

from typing import Sequence

import jax

set_mesh = jax.sharding.set_mesh
named_axis_size = jax.lax.axis_size


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def axis_size(mesh, axis_name: str) -> int:
    """Static size of one mesh axis."""
    return int(mesh.shape[axis_name])
