"""Collective-matmul fusion: overlap a gather-adjacent matmul with its hops.

The two shapes that dominate explicit-TP transformer blocks:

  * **all-gather → matmul** (sequence-parallel FFN entry / QKV): the
    activations are sequence-sharded; the TP all-gather must finish before
    the projection can start — unless the matmul is decomposed per device
    block.  ``allgather_matmul`` runs the staged gather as double-buffered
    ppermute rings (``comms.ring_executor``) and multiplies each block the
    hop it lands, so the whole gather hides behind the MXU.
  * **matmul → reduce-scatter** (TP combine back to sequence shards):
    ``matmul_reduce_scatter`` slices the matmul per output block
    *just-in-time* — the block feeding ring hop t is multiplied while hop
    t-1's partial accumulator is still on the wire.

Both are value-equivalent to the unfused ``collective ∘ matmul`` composition
(each output block is produced by the same block matmul, so AG-side results
are bit-comparable; the RS ring reduces in ring order, hence allclose).  The
fuse-or-not decision lives in ``core.planner.plan_collective_matmul``.

**Backward pass** (custom_vjp): the two shapes are each other's duals, so
the backward collectives reuse the fused rings instead of falling back to
XLA's transpose:

  * ``allgather_matmul``:  dx = matmul_reduce_scatter(Σ-cat(dout), catᵀ(w))
    — the dgrad's ``@ wᵀ`` feeds the RS ring just-in-time, plus the
    gathered-activation cotangent reduce-scattered; dw = gatheredᵀ @ dout
    is local (residuals carry the gathered activations, so no re-gather).
  * ``matmul_reduce_scatter``: (AG(dy), dh) come from ONE fused
    ``allgather_matmul(dy, wᵀ)`` ring — the gather that dgrad needs also
    delivers the gathered cotangent dw = hᵀ @ AG(dy) contracts against.

Stage orders transpose with the collective (the vjp of a stage order is its
reverse — payload duality), and per-stage ``stage_modes`` follow along
reversed.
"""
from __future__ import annotations

import math
from functools import partial
from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from ..comms.ring_executor import (
    _merge_device_axis,
    _resolve_modes,
    _ring_perm,
    _store,
    ring_reduce_scatter_stage,
)
from ..comms.staged_collectives import (
    _ag_finalize,
    _axis_sizes,
    _check_order,
    _permute_blocks_to_order,
)

__all__ = ["allgather_matmul", "matmul_reduce_scatter"]


def _mm(piece: jax.Array, w: jax.Array) -> jax.Array:
    """Contract the trailing feature dim of ``piece`` (any leading/stacked
    dims) with weight ``w`` (d_in, d_out)."""
    return jnp.einsum("...d,df->...f", piece, w)


def _fused_ring_ag_stage(
    cur: jax.Array, outs: List[jax.Array], name: str, ws: Sequence[jax.Array]
) -> Tuple[jax.Array, List[jax.Array]]:
    """One ring all-gather stage that also multiplies every arriving payload.

    ``cur`` is the gathered-so-far data (stacked stage axes leading); ``outs``
    mirror it with the feature dim already projected through each weight.
    Returns the stacked (m, ...) data and outputs — same layout as
    ``lax.all_gather(axis=0, tiled=False)``, so the standard finalize
    transpose applies to both.  The matmul of the block received at hop t
    runs while hop t+1 forwards it: the gather hides behind the MXU.
    """
    m = lax.axis_size(name)
    if m == 1:
        return cur[None], [o[None] for o in outs]
    idx = lax.axis_index(name)
    perm = _ring_perm(m)
    buf = jnp.zeros((m,) + cur.shape, cur.dtype)
    buf = _store(buf, cur, idx)
    obufs = [
        jnp.zeros((m,) + o.shape, o.dtype) for o in outs
    ]
    obufs = [_store(ob, o, idx) for ob, o in zip(obufs, outs)]

    def land(bufs, piece, slot):
        buf, obufs = bufs
        buf = _store(buf, piece, slot)
        obufs = [
            _store(ob, _mm(piece, w), slot) for ob, w in zip(obufs, ws)
        ]
        return buf, obufs

    piece = cur
    for t in range(1, m):
        nxt = lax.ppermute(piece, name, perm)  # forward hop t ...
        if t > 1:
            # ... while the previous delivery is copied AND multiplied
            buf, obufs = land((buf, obufs), piece, (idx - (t - 1)) % m)
        piece = nxt
    buf, obufs = land((buf, obufs), piece, (idx - (m - 1)) % m)
    return buf, obufs


def _oneshot_ag_stage_with_matmul(
    cur: jax.Array, name: str, ws: Sequence[jax.Array]
) -> Tuple[jax.Array, List[jax.Array]]:
    """Blocking-collective fallback for a stage the planner left unfused:
    gather the stacked payloads, then project all of them.  Every block's
    output is still the same block matmul, so values match the fused path."""
    buf = lax.all_gather(cur, name, axis=0, tiled=False)
    return buf, [_mm(buf, w) for w in ws]


def _allgather_matmul_impl(
    x: jax.Array,
    ws: Sequence[jax.Array],
    axis_names: Tuple[str, ...],
    stage_order: Optional[Tuple[str, ...]],
    axis: int,
    stage_modes: Optional[Tuple[str, ...]],
) -> Tuple[jax.Array, Tuple[jax.Array, ...]]:
    axis_names = tuple(axis_names)
    order = (
        _check_order(stage_order, axis_names)
        if stage_order is not None
        else axis_names
    )
    modes = _resolve_modes(stage_modes, len(order))
    ws = list(ws)
    if axis < 0:
        axis += x.ndim

    cur = x
    outs = [_mm(x, wi) for wi in ws]  # local block (overlaps the first send)
    for name, mode in zip(order, modes):
        if mode == "ring":
            cur, outs = _fused_ring_ag_stage(cur, outs, name, ws)
        else:
            cur, outs = _oneshot_ag_stage_with_matmul(cur, name, ws)

    gathered = _merge_device_axis(_ag_finalize(cur, axis_names, order), axis)
    outs = tuple(
        _merge_device_axis(_ag_finalize(o, axis_names, order), axis)
        for o in outs
    )
    return gathered, outs


def _rev(seq: Optional[Tuple[str, ...]]) -> Optional[Tuple[str, ...]]:
    return tuple(reversed(seq)) if seq is not None else None


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _ag_matmul_vjp(axis_names, stage_order, axis, stage_modes, x, ws):
    return _allgather_matmul_impl(x, ws, axis_names, stage_order, axis,
                                  stage_modes)


def _ag_matmul_fwd(axis_names, stage_order, axis, stage_modes, x, ws):
    gathered, outs = _allgather_matmul_impl(
        x, ws, axis_names, stage_order, axis, stage_modes)
    # residuals: the gathered activations double as the dw contraction input
    # (no re-gather in the backward pass) + the weights for dgrad
    return (gathered, outs), (gathered, tuple(ws))


def _ag_matmul_bwd(axis_names, stage_order, axis, stage_modes, res, ct):
    gathered, ws = res
    d_gathered, d_outs = ct
    order = stage_order  # resolved (never None) by the public wrapper
    # dgrad reuses the fused ring as its DUAL: the reversed stage order runs
    # matmul→reduce-scatter with the ``@ wᵀ`` block matmuls feeding the ring
    # just-in-time; multiple weights share one ring via feature concat
    douts_cat = (jnp.concatenate(d_outs, axis=-1) if len(d_outs) > 1
                 else d_outs[0])
    w_cat = (jnp.concatenate(list(ws), axis=-1) if len(ws) > 1 else ws[0])
    dx = _matmul_reduce_scatter_impl(
        douts_cat, jnp.swapaxes(w_cat, 0, 1), axis_names,
        _rev(order), axis, _rev(stage_modes))
    # the gathered-activation output's own cotangent: AG's transpose
    dx = dx + lax.psum_scatter(
        d_gathered, axis_names, scatter_dimension=axis, tiled=True)
    dws = tuple(
        jnp.einsum("...d,...f->df", gathered, do) for do in d_outs
    )
    return dx, dws


_ag_matmul_vjp.defvjp(_ag_matmul_fwd, _ag_matmul_bwd)


def _order_from_plan(plan, axis_names, stage_order):
    """Stage order off a :class:`~repro.core.plan_ir.CollectivePlan` —
    the plan's execution-order axes, validated against ``axis_names``."""
    if plan is None:
        return stage_order
    if stage_order is not None:
        raise ValueError("pass either plan= or stage_order=, not both")
    order = tuple(plan.axes)
    if sorted(order) != sorted(axis_names):
        raise ValueError(
            f"plan axes {order} do not permute the collective axes "
            f"{tuple(axis_names)}")
    return order


def allgather_matmul(
    x: jax.Array,
    w: Union[jax.Array, Sequence[jax.Array]],
    axis_names: Sequence[str],
    *,
    stage_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    stage_modes: Optional[Sequence[str]] = None,
    plan=None,
):
    """``all_gather(x, axis_names, axis=axis, tiled=True) @ w`` with the
    gather overlapped against the per-block matmuls (inside shard_map).

    ``w`` may be one (d, f) weight or a sequence sharing the gather (e.g.
    SwiGLU gate+up): every arriving block is multiplied by each weight while
    the next hop is in flight, and the gathered *activations* ride along —
    the return is ``(gathered_x, out)`` with ``out`` matching ``w``'s
    structure, since TP callers usually need both.

    ``stage_modes`` (per stage, ``"ring"``/``"oneshot"``) follows the
    planner's hop schedule; one-shot stages still produce identical values.
    ``plan`` (a :class:`~repro.core.plan_ir.CollectivePlan`, e.g. from
    ``CommContext.plan("ag", ...)``) supplies the stage order instead.

    Differentiable via custom_vjp: dgrad runs as the fused
    ``matmul_reduce_scatter`` dual (reversed stage order), dw contracts the
    saved gathered activations locally — the backward collectives ride the
    same overlapped rings as the forward.
    """
    if axis < 0:
        axis += x.ndim
    single = not isinstance(w, (list, tuple))
    ws = (w,) if single else tuple(w)
    # resolve the default stage order HERE so the forward impl and the
    # backward's dual derive from one concrete order
    axis_names = tuple(axis_names)
    stage_order = _order_from_plan(plan, axis_names, stage_order)
    order = tuple(stage_order) if stage_order is not None else axis_names
    gathered, outs = _ag_matmul_vjp(
        axis_names,
        order,
        axis,
        tuple(stage_modes) if stage_modes is not None else None,
        x, ws,
    )
    return gathered, (outs[0] if single else tuple(outs))


def _matmul_reduce_scatter_impl(
    h: jax.Array,
    w: jax.Array,
    axis_names: Tuple[str, ...],
    stage_order: Optional[Tuple[str, ...]],
    axis: int,
    stage_modes: Optional[Tuple[str, ...]],
) -> jax.Array:
    axis_names = tuple(axis_names)
    order = (
        _check_order(stage_order, axis_names)
        if stage_order is not None
        else tuple(reversed(axis_names))
    )
    modes = _resolve_modes(stage_modes, len(order))
    sizes = _axis_sizes(axis_names)
    n_total = math.prod(sizes.values())
    if axis < 0:
        axis += h.ndim

    h0 = jnp.moveaxis(h, axis, 0) if axis != 0 else h
    if h0.shape[0] % n_total:
        raise ValueError(
            f"scatter axis length {h0.shape[0]} not divisible by {n_total}"
        )
    # the scatter permutes whole rows, and the matmul is row-wise — so the
    # canonical→stage-order block permutation commutes with it and can be
    # applied to the *input* (no full-size output ever materializes)
    if order != axis_names:
        h0 = _permute_blocks_to_order(h0, axis_names, order, sizes)

    name0 = order[0]
    m = sizes[name0]
    if m == 1 or modes[0] != "ring":
        y = _mm(h0, w)
        y = lax.psum_scatter(y, name0, scatter_dimension=0, tiled=True)
    else:
        blk = h0.shape[0] // m

        def part(b):
            hs = lax.dynamic_slice_in_dim(h0, b * blk, blk, axis=0)
            return _mm(hs, w)  # just-in-time block matmul

        y = ring_reduce_scatter_stage(h0, name0, block_fn=part)

    for name, mode in zip(order[1:], modes[1:]):
        if mode == "ring":
            y = ring_reduce_scatter_stage(y, name)
        else:
            y = lax.psum_scatter(y, name, scatter_dimension=0, tiled=True)
    return jnp.moveaxis(y, 0, axis) if axis != 0 else y


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _mm_rs_vjp(axis_names, stage_order, axis, stage_modes, h, w):
    return _matmul_reduce_scatter_impl(h, w, axis_names, stage_order, axis,
                                       stage_modes)


def _mm_rs_fwd(axis_names, stage_order, axis, stage_modes, h, w):
    y = _matmul_reduce_scatter_impl(h, w, axis_names, stage_order, axis,
                                    stage_modes)
    return y, (h, w)


def _mm_rs_bwd(axis_names, stage_order, axis, stage_modes, res, dy):
    h, w = res
    order = stage_order  # resolved (never None) by the public wrapper
    # ONE fused allgather_matmul ring (the RS dual, reversed stage order)
    # yields both the gathered cotangent AND dgrad: g_dy = AG(dy) feeds dw,
    # dh = AG(dy) @ wᵀ is multiplied per block the hop it lands
    g_dy, (dh,) = _allgather_matmul_impl(
        dy, (jnp.swapaxes(w, 0, 1),), axis_names,
        _rev(order), axis, _rev(stage_modes))
    dw = jnp.einsum("...k,...f->kf", h, g_dy)
    return dh, dw


_mm_rs_vjp.defvjp(_mm_rs_fwd, _mm_rs_bwd)


def matmul_reduce_scatter(
    h: jax.Array,
    w: jax.Array,
    axis_names: Sequence[str],
    *,
    stage_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    stage_modes: Optional[Sequence[str]] = None,
    plan=None,
) -> jax.Array:
    """``psum_scatter(h @ w, axis_names, scatter_dimension=axis, tiled=True)``
    with the matmul decomposed per scattered block (inside shard_map).

    The first reduce-scatter stage runs as a ring whose local partial for
    each departing block is computed *just-in-time*: the slice of ``h``
    feeding hop t is multiplied while hop t-1's accumulator is in flight, so
    the combine's communication hides behind the block matmuls.  Remaining
    stages (smaller payloads, no compute left to hide behind) follow the
    planner's ``stage_modes``.  Values are allclose to the unfused
    composition (ring reduction order).

    Differentiable via custom_vjp: the backward pass is one fused
    ``allgather_matmul`` ring (the RS dual) producing dgrad and the
    gathered cotangent for wgrad together.
    """
    if axis < 0:
        axis += h.ndim
    # resolve the default stage order HERE so the forward impl and the
    # backward's dual derive from one concrete order
    axis_names = tuple(axis_names)
    stage_order = _order_from_plan(plan, axis_names, stage_order)
    order = (tuple(stage_order) if stage_order is not None
             else tuple(reversed(axis_names)))
    return _mm_rs_vjp(
        axis_names,
        order,
        axis,
        tuple(stage_modes) if stage_modes is not None else None,
        h, w,
    )
