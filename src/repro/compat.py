"""Two shims over JAX (0.9) that carry this project's defaults.

  * ``make_mesh`` — ``jax.make_mesh`` with Auto axis types.  JAX's own
    default is Explicit; the GSPMD paths and the planned collectives here
    expect Auto axes.
  * ``shard_map`` — ``jax.shard_map`` with replication checking off.
"""
from __future__ import annotations

from typing import Sequence

import jax

__all__ = ["make_mesh", "shard_map"]


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices=None,
) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with every axis Auto."""
    axis_shapes = tuple(axis_shapes)
    return jax.make_mesh(
        axis_shapes, tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_shapes),
        devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking off."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
