"""Baseline collectives (paper §IV competitors) + hierarchical all-reduce.

All primitives here run *inside* shard_map.  ``ring_all_gather`` and
``neighbor_exchange_all_gather`` are TPU-native ports of the paper's Ring and
NE baselines (ppermute wavefronts); ``one_stage_all_gather`` is the paper's
one-stage model — a single flat collective.  ``hierarchical_all_reduce`` is
the OpTree-style staged gradient sync used by the ZeRO-1 optimizer: the slow
(pod/DCN) axis only ever carries the already-scattered shard — the direct
analogue of OpTree stage 1 carrying a single item per node.

Ring/NE unroll their step loops in Python: they are reference baselines for
correctness tests and small axes; the staged/XLA paths are the scale paths.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .staged_allgather import staged_all_gather
from .staged_collectives import staged_reduce_scatter

__all__ = [
    "ring_all_gather",
    "neighbor_exchange_all_gather",
    "one_stage_all_gather",
    "reduce_scatter",
    "hierarchical_all_reduce",
]


def one_stage_all_gather(x: jax.Array, axis_names, axis: int = 0) -> jax.Array:
    """The paper's one-stage model: a single flat all-gather."""
    names = (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)
    return lax.all_gather(x, names, axis=axis, tiled=True)


def reduce_scatter(x: jax.Array, axis_name: str, axis: int = 0) -> jax.Array:
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


def ring_all_gather(x: jax.Array, axis_name: str, axis: int = 0) -> jax.Array:
    """Classic N-1-step ring all-gather via ppermute (paper's Ring baseline)."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    x0 = jnp.moveaxis(x, axis, 0) if axis != 0 else x
    buf = jnp.zeros((n,) + x0.shape, x0.dtype)
    buf = lax.dynamic_update_slice(buf, x0[None], (idx,) + (0,) * x0.ndim)

    def body(t, carry):
        cur, buf = carry
        cur = lax.ppermute(cur, axis_name, perm)
        src = (idx - t) % n  # origin of the block arriving at step t
        buf = lax.dynamic_update_slice(buf, cur[None], (src,) + (0,) * cur.ndim)
        return cur, buf

    _, buf = lax.fori_loop(1, n, body, (x0, buf))
    out = buf.reshape((n * x0.shape[0],) + x0.shape[1:])
    return jnp.moveaxis(out, 0, axis) if axis != 0 else out


def _ne_tables(n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Pair-index bookkeeping for neighbor exchange.

    h[t, i] = index of the pair (block 2h, 2h+1) node i *received* at step t
    (h[0] = own pair after the first exchange).  partner[t, i] = neighbour
    exchanged with at step t.
    """
    steps = n // 2
    h = np.zeros((steps, n), dtype=np.int64)
    partner = np.zeros((steps, n), dtype=np.int64)
    h[0] = np.arange(n) // 2
    partner[0] = np.arange(n) ^ 1
    for t in range(1, steps):
        if t % 2 == 1:  # odd pairing: (1,2),(3,4),...,(n-1,0)
            p = np.where(np.arange(n) % 2 == 1, (np.arange(n) + 1) % n, (np.arange(n) - 1) % n)
        else:  # even pairing: (0,1),(2,3),...
            p = np.arange(n) ^ 1
        partner[t] = p
        h[t] = h[t - 1][p]
    return h, partner


def neighbor_exchange_all_gather(x: jax.Array, axis_name: str, axis: int = 0) -> jax.Array:
    """Neighbor-Exchange all-gather (Chen et al. 2005): N/2 exchange steps."""
    n = lax.axis_size(axis_name)
    if n % 2:
        raise ValueError("neighbor exchange needs an even axis size")
    if n == 2:
        return one_stage_all_gather(x, axis_name, axis=axis)
    idx = lax.axis_index(axis_name)
    h_np, partner_np = _ne_tables(n)
    h = jnp.asarray(h_np)

    x0 = jnp.moveaxis(x, axis, 0) if axis != 0 else x
    buf = jnp.zeros((n,) + x0.shape, x0.dtype)
    buf = lax.dynamic_update_slice(buf, x0[None], (idx,) + (0,) * x0.ndim)

    # step 0: swap own single block with the even-pairing partner
    perm0 = [(i, int(partner_np[0, i])) for i in range(n)]
    recv = lax.ppermute(x0, axis_name, perm0)
    buf = lax.dynamic_update_slice(
        buf, recv[None], (jnp.asarray(partner_np[0])[idx],) + (0,) * x0.ndim
    )

    # steps 1..n/2-1: forward the pair received last step (pair h[t-1])
    for t in range(1, n // 2):
        send_start = 2 * h[t - 1][idx]
        block = lax.dynamic_slice(
            buf, (send_start,) + (0,) * x0.ndim, (2,) + x0.shape
        )
        perm = [(i, int(partner_np[t, i])) for i in range(n)]
        got = lax.ppermute(block, axis_name, perm)
        buf = lax.dynamic_update_slice(
            buf, got, (2 * h[t][idx],) + (0,) * x0.ndim
        )

    out = buf.reshape((n * x0.shape[0],) + x0.shape[1:])
    return jnp.moveaxis(out, 0, axis) if axis != 0 else out


def hierarchical_all_reduce(
    x: jax.Array,
    fast_axes: Sequence[str],
    slow_axes: Sequence[str] = (),
    *,
    gather: bool = True,
    num_chunks: int = 1,
) -> jax.Array:
    """OpTree-staged all-reduce: reduce-scatter over the fast (ICI) axes,
    psum over the slow (pod/DCN) axes on the scattered shard, then staged
    all-gather back (slow axis never sees the full payload).

    With ``gather=False`` the result stays scattered over ``fast_axes`` —
    the ZeRO-1 form (optimizer updates the shard, parameters are gathered
    later by `optree_all_gather`).  The scatter runs in canonical
    (major-first) block order, so the scattered shard is exactly
    ``psum_scatter(x, fast_axes)``'s block for this device.
    """
    fast_axes = tuple(fast_axes)
    slow_axes = tuple(slow_axes)
    y = staged_reduce_scatter(x, fast_axes, num_chunks=num_chunks)
    if slow_axes:
        y = lax.psum(y, slow_axes)
    if gather:
        y = staged_all_gather(y, fast_axes)  # major-first (paper order)
    return y
