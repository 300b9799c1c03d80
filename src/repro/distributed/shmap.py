"""shard_map as every distributed driver in this repo calls it."""
from __future__ import annotations

import jax


def axis_size(axis: str):
    """Size of a mapped mesh axis."""
    return jax.lax.axis_size(axis)


def shard_map_compat(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
