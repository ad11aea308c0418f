"""The two shard_map flavours and the mesh builder every SPMD entry
point goes through, so the keyword choices live in one place."""
from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    """jax.shard_map with value-and-replication checking disabled."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def partial_shard_map(f, mesh, in_specs, out_specs, manual_axes):
    """Partial-auto shard_map: manual over ``manual_axes``, GSPMD-auto
    over the rest (the decoupled train step's model axis)."""
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=out_specs,
        axis_names=set(manual_axes),
        check_vma=False,
    )


def make_mesh(axis_shapes, axis_names):
    """jax.make_mesh with every axis Auto."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )
