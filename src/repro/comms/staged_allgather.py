"""Staged (OpTree) all-gather over factorized mesh axes.

``staged_all_gather`` is the *inside-shard_map* primitive: it runs the
paper's k stages as a sequence of single-sub-axis all-gathers.  Gathering
minor-to-major needs no data movement beyond the collectives themselves;
any other stage order (e.g. the OpTree-optimal "slow/major axis first while
the payload is small") is followed by one local transpose to restore the
canonical order — layout work, not communication.

``optree_all_gather`` is the user-facing wrapper: plans the stage order from
the cost model (core.planner ≙ Theorem 2) and wraps shard_map.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..compat import shard_map
from ..core.planner import ICI_LINK, DCN_LINK, LinkSpec, plan_axis_order
from ..obs import stage_scope

__all__ = ["staged_all_gather", "canonical_all_gather", "optree_all_gather",
           "link_for_axis", "names_for_plan"]


def link_for_axis(name: str, links: Optional[dict] = None) -> LinkSpec:
    """Link model for a mesh axis: explicit map wins, else 'pod*' names are
    DCN-class and everything else ICI."""
    if links and name in links:
        return links[name]
    return DCN_LINK if name.startswith("pod") else ICI_LINK


def names_for_plan(plan, axis_names, sizes, links=None):
    """Map a planned (size, link) stage sequence back to axis names (stable
    for duplicate (size, link) pairs)."""
    remaining = list(axis_names)
    order = []
    for st in plan.stages:
        for n in remaining:
            if sizes[n] == st.factor and link_for_axis(n, links).name == st.link.name:
                order.append(n)
                remaining.remove(n)
                break
    assert not remaining, (order, remaining)
    return tuple(order)


def staged_all_gather(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    stage_order: Optional[Sequence[str]] = None,
    axis: int = 0,
) -> jax.Array:
    """k-stage all-gather inside shard_map.

    Args:
      x: local shard.
      axis_names: the factorized sub-axes of the logical gather axis,
        *major first* (mesh order).  ``prod(sizes) = N``.
      stage_order: the order stages execute (default: paper order — major
        first, i.e. slowest/most-distant links carry the smallest payload).
      axis: array axis to gather along.

    Returns the same value as ``jax.lax.all_gather(x, tuple(axis_names),
    axis=axis, tiled=True)`` — i.e. blocks concatenated in canonical
    (major-first) device order.
    """
    axis_names = tuple(axis_names)
    order = tuple(stage_order) if stage_order is not None else axis_names
    if sorted(order) != sorted(axis_names):
        raise ValueError(f"stage_order {order} must permute {axis_names}")

    if order == tuple(reversed(axis_names)):
        # minor-to-major: tiled gathers compose to canonical order directly
        y = x
        for k, name in enumerate(order):
            with stage_scope(k, name, "oneshot"):
                y = jax.lax.all_gather(y, name, axis=axis, tiled=True)
        return y

    # general order: stack stages as leading axes, then one local fix-up
    y = x
    for k, name in enumerate(order):
        with stage_scope(k, name, "oneshot"):
            y = jax.lax.all_gather(y, name, axis=0, tiled=False)
    # leading stacked axes are reversed(order); want axis_names order
    stacked = tuple(reversed(order))
    perm_named = tuple(stacked.index(n) for n in axis_names)
    rest = tuple(range(len(axis_names), y.ndim))
    y = jnp.transpose(y, perm_named + rest)
    # collapse the k stacked axes into the target axis
    k = len(axis_names)
    gathered = math.prod(y.shape[:k])
    y = y.reshape((gathered,) + y.shape[k:])  # (N, *x.shape)
    # merge into `axis`: (N, ..., s, ...) -> (..., N*s, ...)
    if axis != 0:
        y = jnp.moveaxis(y, 0, axis)
        pre = y.shape[:axis]
        y = y.reshape(pre + (y.shape[axis] * y.shape[axis + 1],) + y.shape[axis + 2 :])
    else:
        y = y.reshape((y.shape[0] * y.shape[1],) + y.shape[2:])
    return y


def canonical_all_gather(x: jax.Array, axis_names: Sequence[str], axis: int = 0) -> jax.Array:
    """XLA's own single-shot all-gather over the product axis (baseline)."""
    return jax.lax.all_gather(x, tuple(axis_names), axis=axis, tiled=True)


def optree_all_gather(
    x: jax.Array,
    mesh: Mesh,
    axis_names: Sequence[str],
    *,
    links: Optional[dict] = None,
    axis: int = 0,
    in_spec: Optional[P] = None,
    out_spec: Optional[P] = None,
) -> jax.Array:
    """User-facing staged all-gather: plans the stage order (Theorem 2
    analogue) and runs it under shard_map.

    Args:
      x: globally-sharded array (sharded along ``axis`` over ``axis_names``).
      links: optional map axis_name -> LinkSpec (defaults: 'pod*' -> DCN,
        else ICI) for the planner.
    """
    axis_names = tuple(axis_names)
    sizes = {n: mesh.shape[n] for n in axis_names}

    shard_bytes = x.size * x.dtype.itemsize / math.prod(sizes.values())
    axes = [(sizes[n], link_for_axis(n, links)) for n in axis_names]
    plan = plan_axis_order(axes, shard_bytes)
    order = names_for_plan(plan, axis_names, sizes, links)

    ispec = in_spec if in_spec is not None else P(axis_names)
    ospec = out_spec if out_spec is not None else P()

    fn = shard_map(
        lambda y: staged_all_gather(y, axis_names, stage_order=order, axis=axis),
        mesh=mesh,
        in_specs=ispec,
        out_specs=ospec,
    )
    return fn(x)
