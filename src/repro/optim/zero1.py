"""Explicit ZeRO-1 gradient sharding via the context-planned reduce-scatter.

The pjit path (``opt_state_specs``) expresses ZeRO-1 as sharding specs and
lets GSPMD emit the collectives.  This module is the shard_map form used by
explicit-DP training loops: gradients are reduce-scattered over the data
axes through the active :class:`repro.comms.api.CommContext` (OpTree stage
order — slow axes last, carrying only the final 1/N shard), each rank
updates its optimizer shard, and parameters are re-gathered with the
context all-gather.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax
from jax import lax

from ..comms import api

__all__ = ["zero1_shard_grads", "zero1_unshard_params"]


def _dp_size(fast_axes: Sequence[str]) -> int:
    return math.prod(lax.axis_size(n) for n in fast_axes)


def zero1_shard_grads(
    grads,
    fast_axes: Sequence[str],
    slow_axes: Sequence[str] = (),
    *,
    num_chunks: int = 1,
):
    """Reduce-scatter every gradient leaf over the data axes (ZeRO-1).

    Each DP rank ends with the leading-dim shard it owns for the optimizer
    update; slow (pod/DCN) axes are reduced on the already-scattered shard
    so they never carry the full gradient.  Leaves whose leading dim is not
    divisible by the DP size fall back to a full psum (replicated update) —
    same contract as the spec-based ``opt_state_specs`` path.
    """
    fast_axes = tuple(fast_axes)
    slow_axes = tuple(slow_axes)
    n = _dp_size(fast_axes)

    def shard(g):
        if g.ndim and g.shape[0] % n == 0:
            y = api.reduce_scatter(
                g, axes=fast_axes, num_chunks=api.legacy_chunks(num_chunks))
            return lax.psum(y, slow_axes) if slow_axes else y
        return lax.psum(g, fast_axes + slow_axes)

    return jax.tree.map(shard, grads)


def zero1_unshard_params(
    params,
    fast_axes: Sequence[str],
    *,
    reference=None,
):
    """Staged all-gather of updated parameter shards back to replicated.

    ``reference`` (the matching pre-scatter pytree, e.g. the full params)
    tells which leaves ``zero1_shard_grads`` actually scattered — leaves
    that fell back to a replicated psum are returned unchanged.  Without a
    reference every leaf is gathered (caller guarantees a uniform tree).
    """
    fast_axes = tuple(fast_axes)

    if reference is None:
        return jax.tree.map(
            lambda p: api.all_gather(p, axes=fast_axes), params)

    def gather(p, full):
        if p.ndim and p.shape[0] != full.shape[0]:
            return api.all_gather(p, axes=fast_axes)
        return p

    return jax.tree.map(gather, params, reference)
