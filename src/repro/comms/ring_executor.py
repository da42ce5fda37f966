"""Per-hop ring executor: double-buffered ppermute schedules for the staged
engine.

PR 1's staged collectives issue one blocking XLA collective per stage —
Eq. 3's ``(d/B + a)·S`` with every stage a barrier.  This module is the
execution layer below that granularity: each stage runs as an explicit ring
of ``ppermute`` hops, structured so the block received at hop t is
*forwarded* at hop t+1 while its local copy (all-gather) or local
reduce/add (reduce-scatter) runs concurrently — the double-buffering that
``core.planner.perhop_stage_time`` models (α amortized across in-flight
hops, only the longer of the serialization/launch chains exposed).

Every executor composes stage-by-stage exactly like the staged primitives in
``staged_collectives.py`` (stacking form + one local fix-up for AG; one
local block permutation for RS), so any planner stage order is supported and
the results are bit-identical to the XLA one-shot collectives (all-reduce:
identical up to reduction order).  ``stage_modes`` lets the planner pick the
executor per stage: ``"ring"`` (per-hop ppermute) where the overlap model
wins, ``"oneshot"`` (the blocking XLA collective) where a stage is too small
to pipeline — see ``core.planner.choose_hop_schedule``.
"""
from __future__ import annotations

import math
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .staged_collectives import (
    _a2a_merge_digits,
    _a2a_split_digits,
    _ag_finalize,
    _axis_sizes,
    _check_order,
    _permute_blocks_to_order,
    _split_rs_chunks,
    _wavefront,
)

__all__ = [
    "FaultInjection",
    "fault_injection",
    "ring_all_gather_stage",
    "ring_reduce_scatter_stage",
    "ring_all_to_all_stage",
    "perhop_all_gather",
    "perhop_reduce_scatter",
    "perhop_all_reduce",
    "perhop_all_to_all",
    "hybrid_all_gather",
    "hybrid_reduce_scatter",
    "hybrid_all_reduce",
    "hybrid_all_to_all",
]


def _ring_perm(m: int) -> List[Tuple[int, int]]:
    return [(i, (i + 1) % m) for i in range(m)]


# --------------------------------------------------------------------------
# fault injection (chaos harness hook)
# --------------------------------------------------------------------------

@dataclass
class FaultInjection:
    """Corrupt a chosen ppermute hop of a ring stage, for chaos tests.

    ``axis`` is the mesh axis whose ring stage to hit, ``hop`` the 1-based
    hop index within the stage, ``mode`` either ``"drop"`` (the received
    block arrives zeroed — a lost lightpath) or ``"corrupt"`` (+1 to every
    element — a payload bit flip).  ``times`` bounds how many matching hop
    *traces* are corrupted: the executor's bounded retry re-traces the
    stage per attempt, so ``times=1`` means only the first attempt sees the
    fault (the retry genuinely recovers) while a large ``times`` keeps
    every attempt faulty (forcing the one-shot fallback).  ``device``
    optionally restricts the fault to one position on the ring.
    """

    axis: str
    hop: int = 1
    mode: str = "drop"
    times: int = 1
    device: Optional[int] = None
    applied: int = 0  # mutable: matching hop traces consumed so far

    def __post_init__(self) -> None:
        if self.mode not in ("drop", "corrupt"):
            raise ValueError(f"mode must be drop|corrupt, got {self.mode!r}")


_INJECTIONS: List[FaultInjection] = []


@contextmanager
def fault_injection(spec: FaultInjection):
    """Activate ``spec`` for every ring stage traced inside the block."""
    _INJECTIONS.append(spec)
    try:
        yield spec
    finally:
        _INJECTIONS.remove(spec)


def _maybe_inject(recv: jax.Array, name: str, hop: int) -> jax.Array:
    """Pass a just-received ppermute block through the active injections."""
    for spec in _INJECTIONS:
        if spec.axis != name or spec.hop != hop or spec.applied >= spec.times:
            continue
        spec.applied += 1
        if spec.mode == "drop":
            bad = jnp.zeros_like(recv)
        else:
            bad = recv + jnp.ones_like(recv)
        if spec.device is None:
            recv = bad
        else:
            recv = jnp.where(lax.axis_index(name) == spec.device, bad, recv)
    return recv


def _store(buf: jax.Array, piece: jax.Array, slot) -> jax.Array:
    return lax.dynamic_update_slice(
        buf, piece[None], (slot,) + (0,) * piece.ndim
    )


def ring_all_gather_stage(x: jax.Array, name: str) -> jax.Array:
    """One ring all-gather stage in stacking form: equals
    ``lax.all_gather(x, name, axis=0, tiled=False)``.

    m-1 ppermute hops, double-buffered: the block received at hop t is
    forwarded at hop t+1 while only being *referenced* locally (pieces are
    collected in arrival order — origin ``idx - t``), so nothing serializes
    against the sends.  One flip+roll at the end rotates arrival order into
    origin order — a single local copy instead of m buffer updates.
    """
    m = lax.axis_size(name)
    if m == 1:
        return x[None]
    idx = lax.axis_index(name)
    perm = _ring_perm(m)
    pieces = [x]  # arrival order: origin idx, idx-1, ..., idx-(m-1)
    for t in range(1, m):
        pieces.append(_maybe_inject(lax.ppermute(pieces[-1], name, perm),
                                    name, t))
    # arrival[t] holds origin (idx - t) mod m; flipping gives origin
    # (idx + 1 + j) mod m at slot j, and rolling by idx+1 lands origin j
    # at slot j — the all_gather stacking order
    stacked = jnp.flip(jnp.stack(pieces, axis=0), axis=0)
    return jnp.roll(stacked, idx + 1, axis=0)


def ring_reduce_scatter_stage(
    y: jax.Array, name: str, *, block_fn=None
) -> jax.Array:
    """One ring reduce-scatter stage: equals ``lax.psum_scatter(y, name,
    scatter_dimension=0, tiled=True)`` up to reduction order (exact for
    exactly-representable sums).

    The accumulator for block b travels the ring b+1 → ... → b, gaining one
    local contribution per hop; the local block's slice+add for hop t runs
    while hop t's ppermute is in flight.

    ``block_fn(b)`` overrides the local-contribution provider (default: the
    b-th of m contiguous slices of ``y``) — the collective-matmul fusion
    plugs in a just-in-time block matmul here.
    """
    m = lax.axis_size(name)
    if m == 1:
        return y if block_fn is None else block_fn(0)
    if block_fn is None:
        if y.shape[0] % m:
            raise ValueError(
                f"length {y.shape[0]} not divisible by ring size {m}"
            )
        blk = y.shape[0] // m

        def block_fn(b):
            return lax.dynamic_slice_in_dim(y, b * blk, blk, axis=0)

    idx = lax.axis_index(name)
    perm = _ring_perm(m)
    acc = block_fn((idx - 1) % m)  # own contribution to the departing block
    for s in range(1, m):
        recv = _maybe_inject(lax.ppermute(acc, name, perm), name, s)
        acc = recv + block_fn((idx - s - 1) % m)
    return acc


def ring_all_to_all_stage(y: jax.Array, name: str) -> jax.Array:
    """One ring all-to-all digit transpose on the leading (m, ...) axis:
    equals ``lax.all_to_all(y, name, split_axis=0, concat_axis=0,
    tiled=True)`` bit for bit.

    m-1 ppermute hops, hop t carrying exactly the slices whose digit shift
    is t: device q ships its resident slice (q+t) mod m along the rotation
    q → (q+t) mod m, and receiver r files the arrival under origin
    (r-t) mod m.  Unlike the gather ring there is NO forwarding chain —
    every hop sends a distinct locally-resident slice, the causal
    independence the per-hop overlap model prices.  Arrival slot t holds
    origin (idx - t) mod m, so the same flip+roll as the all-gather ring
    restores origin order in one local copy.
    """
    m = lax.axis_size(name)
    if m == 1:
        return y
    if y.shape[0] != m:
        raise ValueError(f"digit axis {y.shape[0]} != ring size {m}")
    idx = lax.axis_index(name)
    pieces = [lax.dynamic_index_in_dim(y, idx, axis=0, keepdims=False)]
    for t in range(1, m):
        send = lax.dynamic_index_in_dim(
            y, (idx + t) % m, axis=0, keepdims=False
        )
        perm = [(i, (i + t) % m) for i in range(m)]
        pieces.append(_maybe_inject(lax.ppermute(send, name, perm), name, t))
    stacked = jnp.flip(jnp.stack(pieces, axis=0), axis=0)
    return jnp.roll(stacked, idx + 1, axis=0)


def _resolve_modes(
    stage_modes: Optional[Sequence[str]], k: int
) -> Tuple[str, ...]:
    if stage_modes is None:
        return ("ring",) * k
    modes = tuple(stage_modes)
    if len(modes) != k or any(m not in ("ring", "oneshot") for m in modes):
        raise ValueError(
            f"stage_modes must be {k} of 'ring'|'oneshot', got {modes}"
        )
    return modes


def _merge_device_axis(y: jax.Array, axis: int) -> jax.Array:
    """Fold a leading (N,) device-block axis into local axis ``axis``."""
    if axis == 0:
        return y.reshape((y.shape[0] * y.shape[1],) + y.shape[2:])
    y = jnp.moveaxis(y, 0, axis)
    pre = y.shape[:axis]
    return y.reshape(pre + (y.shape[axis] * y.shape[axis + 1],) + y.shape[axis + 2:])


def perhop_all_gather(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    stage_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    stage_modes: Optional[Sequence[str]] = None,
    stage_probe: Optional[Callable] = None,
) -> jax.Array:
    """Per-hop staged all-gather inside shard_map: bit-identical to
    ``lax.all_gather(x, tuple(axis_names), axis=axis, tiled=True)``.

    Stages run in ``stage_order`` (default major-first, the paper order),
    each as a double-buffered ppermute ring (or the blocking XLA collective
    where ``stage_modes`` says ``"oneshot"``); the stacked stage axes are
    collapsed to canonical device order by one local transpose at the end.

    ``stage_probe(before, after, name)`` is called once per stage with the
    stage's traced input/output — the hook the verified executor uses for
    per-stage conservation checksums.
    """
    axis_names = tuple(axis_names)
    order = (
        _check_order(stage_order, axis_names)
        if stage_order is not None
        else axis_names
    )
    modes = _resolve_modes(stage_modes, len(order))

    if axis < 0:
        axis += x.ndim
    y = x
    for name, mode in zip(order, modes):
        before = y
        if mode == "ring":
            y = ring_all_gather_stage(y, name)
        else:
            y = lax.all_gather(y, name, axis=0, tiled=False)
        if stage_probe is not None:
            stage_probe(before, y, name)
    y = _ag_finalize(y, axis_names, order)  # (N, *x.shape)
    return _merge_device_axis(y, axis)


def perhop_reduce_scatter(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    stage_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    stage_modes: Optional[Sequence[str]] = None,
    stage_probe: Optional[Callable] = None,
) -> jax.Array:
    """Per-hop staged reduce-scatter: equals ``lax.psum_scatter(x,
    tuple(axis_names), scatter_dimension=axis, tiled=True)`` (bit-identical
    for exactly-representable sums; ring stages reduce in ring order).

    Default stage order is the paper-optimal reverse (slow axes last, on the
    smallest payload); any order composes via the same local pre-permutation
    ``staged_reduce_scatter`` uses.
    """
    axis_names = tuple(axis_names)
    order = (
        _check_order(stage_order, axis_names)
        if stage_order is not None
        else tuple(reversed(axis_names))
    )
    modes = _resolve_modes(stage_modes, len(order))
    sizes = _axis_sizes(axis_names)

    if axis < 0:
        axis += x.ndim
    y = jnp.moveaxis(x, axis, 0) if axis != 0 else x
    n_total = math.prod(sizes.values())
    if y.shape[0] % n_total:
        raise ValueError(
            f"axis length {y.shape[0]} not divisible by devices {n_total}"
        )
    if order != axis_names:
        y = _permute_blocks_to_order(y, axis_names, order, sizes)
    for name, mode in zip(order, modes):
        before = y
        if mode == "ring":
            y = ring_reduce_scatter_stage(y, name)
        else:
            y = lax.psum_scatter(y, name, scatter_dimension=0, tiled=True)
        if stage_probe is not None:
            stage_probe(before, y, name)
    return jnp.moveaxis(y, 0, axis) if axis != 0 else y


def _a2a_stage_dispatch(y, name, dim, mode):
    """One a2a digit transpose on digit axis ``dim``: a double-buffered
    ppermute rotation ("ring") or the blocking XLA collective ("oneshot")."""
    if mode == "ring":
        y = jnp.moveaxis(y, dim, 0) if dim != 0 else y
        y = ring_all_to_all_stage(y, name)
        return jnp.moveaxis(y, 0, dim) if dim != 0 else y
    return lax.all_to_all(y, name, split_axis=dim, concat_axis=dim, tiled=True)


def perhop_all_to_all(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    stage_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    stage_modes: Optional[Sequence[str]] = None,
    stage_probe: Optional[Callable] = None,
) -> jax.Array:
    """Per-hop staged all-to-all inside shard_map: bit-identical to
    ``lax.all_to_all(x, tuple(axis_names), split_axis=axis,
    concat_axis=axis, tiled=True)``.

    The N-block exchange factorizes into k per-sub-axis digit transposes
    that commute — any ``stage_order`` yields the identical output (no
    finalize transpose needed, unlike the gather family); only the modeled
    cost differs.  Each stage runs as a ppermute rotation ring or the
    blocking XLA collective per ``stage_modes``.
    """
    axis_names = tuple(axis_names)
    order = (
        _check_order(stage_order, axis_names)
        if stage_order is not None
        else axis_names
    )
    modes = _resolve_modes(stage_modes, len(order))
    sizes = _axis_sizes(axis_names)
    k = len(axis_names)

    if axis < 0:
        axis += x.ndim
    y = jnp.moveaxis(x, axis, 0) if axis != 0 else x
    shaped = _a2a_split_digits(y, axis_names, sizes)
    for name, mode in zip(order, modes):
        before = shaped
        shaped = _a2a_stage_dispatch(shaped, name, axis_names.index(name), mode)
        if stage_probe is not None:
            stage_probe(before, shaped, name)
    out = _a2a_merge_digits(shaped, k)
    return jnp.moveaxis(out, 0, axis) if axis != 0 else out


# --------------------------------------------------------------------------
# hybrid execution: the chunk wavefront OVER per-hop ring stages
# --------------------------------------------------------------------------
#
# ``staged_collectives`` pipelines C chunks over BLOCKING whole-stage
# collectives; the executors below run the same wavefront with each stage
# dispatched per its planner stage mode — a "ring" stage is the
# double-buffered ppermute ring, an "oneshot" stage the XLA collective — so
# chunk i's stage j overlaps chunk i-1's stage j+1 AND every ring stage's
# hops double-buffer internally.  This is the IR's ``hybrid`` plan mode
# (``core.planner.choose_hop_schedule`` emits it when its modeled makespan
# beats both pure modes); outputs stay bit-identical to the XLA one-shot
# collectives exactly like the pure paths (ring AG == all_gather stacking
# form; ring RS reduces in ring order — exact for exactly-representable
# sums).

def _hyb_ag_stage(ch: jax.Array, name: str, mode: str) -> jax.Array:
    if mode == "ring":
        return ring_all_gather_stage(ch, name)
    return lax.all_gather(ch, name, axis=0, tiled=False)


def _hyb_rs_stage(ch: jax.Array, name: str, mode: str) -> jax.Array:
    if mode == "ring":
        return ring_reduce_scatter_stage(ch, name)
    return lax.psum_scatter(ch, name, scatter_dimension=0, tiled=True)


def hybrid_all_gather(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    stage_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    num_chunks: int = 2,
    stage_modes: Optional[Sequence[str]] = None,
) -> jax.Array:
    """Chunk-wavefront per-hop staged all-gather: equals
    ``lax.all_gather(x, tuple(axis_names), axis=axis, tiled=True)`` bit for
    bit (same chunk interleave as ``staged_all_gather_chunked``, same ring
    stages as ``perhop_all_gather``)."""
    axis_names = tuple(axis_names)
    order = (
        _check_order(stage_order, axis_names)
        if stage_order is not None
        else axis_names
    )
    modes = _resolve_modes(stage_modes, len(order))

    if axis < 0:
        axis += x.ndim
    y = jnp.moveaxis(x, axis, 0) if axis != 0 else x
    shard = y.shape[0]
    if shard % num_chunks:
        raise ValueError(f"shard length {shard} not divisible by {num_chunks}")
    per_chunk = shard // num_chunks
    chunks = [y[c * per_chunk:(c + 1) * per_chunk] for c in range(num_chunks)]
    chunks = _wavefront(
        chunks, len(order),
        lambda ch, j: _hyb_ag_stage(ch, order[j], modes[j]),
    )
    gathered = [_ag_finalize(ch, axis_names, order) for ch in chunks]
    out = jnp.stack(gathered, axis=1)  # (N, C, per_chunk, ...)
    n_total = out.shape[0]
    out = out.reshape((n_total * shard,) + out.shape[3:])
    return jnp.moveaxis(out, 0, axis) if axis != 0 else out


def hybrid_reduce_scatter(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    stage_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    num_chunks: int = 2,
    stage_modes: Optional[Sequence[str]] = None,
) -> jax.Array:
    """Chunk-wavefront per-hop staged reduce-scatter: equals
    ``lax.psum_scatter(x, tuple(axis_names), scatter_dimension=axis,
    tiled=True)`` (exact for exactly-representable sums)."""
    axis_names = tuple(axis_names)
    order = (
        _check_order(stage_order, axis_names)
        if stage_order is not None
        else tuple(reversed(axis_names))
    )
    modes = _resolve_modes(stage_modes, len(order))
    sizes = _axis_sizes(axis_names)

    if axis < 0:
        axis += x.ndim
    y = jnp.moveaxis(x, axis, 0) if axis != 0 else x
    chunks = _split_rs_chunks(y, axis_names, order, sizes, num_chunks)
    chunks = _wavefront(
        chunks, len(order),
        lambda ch, j: _hyb_rs_stage(ch, order[j], modes[j]),
    )
    out = chunks[0] if num_chunks == 1 else jnp.concatenate(chunks, axis=0)
    return jnp.moveaxis(out, 0, axis) if axis != 0 else out


def hybrid_all_to_all(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    stage_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    num_chunks: int = 2,
    stage_modes: Optional[Sequence[str]] = None,
) -> jax.Array:
    """Chunk-wavefront per-hop staged all-to-all: equals
    ``lax.all_to_all(x, tuple(axis_names), split_axis=axis,
    concat_axis=axis, tiled=True)`` bit for bit (same block-interior chunk
    split as ``staged_all_to_all``, same digit-transpose stages as
    ``perhop_all_to_all``)."""
    axis_names = tuple(axis_names)
    order = (
        _check_order(stage_order, axis_names)
        if stage_order is not None
        else axis_names
    )
    modes = _resolve_modes(stage_modes, len(order))
    sizes = _axis_sizes(axis_names)
    k = len(axis_names)

    if axis < 0:
        axis += x.ndim
    y = jnp.moveaxis(x, axis, 0) if axis != 0 else x
    shaped = _a2a_split_digits(y, axis_names, sizes)
    block = shaped.shape[k]
    if block % num_chunks:
        raise ValueError(
            f"block interior {block} not divisible by {num_chunks} chunks"
        )
    per = block // num_chunks
    chunks = [
        lax.slice_in_dim(shaped, c * per, (c + 1) * per, axis=k)
        for c in range(num_chunks)
    ]
    chunks = _wavefront(
        chunks, k,
        lambda ch, j: _a2a_stage_dispatch(
            ch, order[j], axis_names.index(order[j]), modes[j]),
    )
    out = chunks[0] if num_chunks == 1 else jnp.concatenate(chunks, axis=k)
    out = _a2a_merge_digits(out, k)
    return jnp.moveaxis(out, 0, axis) if axis != 0 else out


def hybrid_all_reduce(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    rs_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    num_chunks: int = 2,
    stage_modes: Optional[Sequence[str]] = None,
) -> jax.Array:
    """Chunk-wavefront per-hop staged all-reduce (RS then AG over one plan,
    the 2k-stage chain pipelined across chunks): equals ``lax.psum(x,
    tuple(axis_names))`` up to ring-stage reduction order.  ``stage_modes``
    covers the full 2k-stage chain, matching
    ``choose_hop_schedule(..., collective="ar")``."""
    axis_names = tuple(axis_names)
    order = (
        _check_order(rs_order, axis_names)
        if rs_order is not None
        else tuple(reversed(axis_names))
    )
    ag_order = tuple(reversed(order))
    k = len(axis_names)
    modes = _resolve_modes(stage_modes, 2 * k)
    sizes = _axis_sizes(axis_names)

    if axis < 0:
        axis += x.ndim
    y = jnp.moveaxis(x, axis, 0) if axis != 0 else x
    length = y.shape[0]
    chunks = _split_rs_chunks(y, axis_names, order, sizes, num_chunks)

    def apply_stage(ch, j):
        if j < k:
            return _hyb_rs_stage(ch, order[j], modes[j])
        return _hyb_ag_stage(ch, ag_order[j - k], modes[j])

    chunks = _wavefront(chunks, 2 * k, apply_stage)
    gathered = [_ag_finalize(ch, axis_names, ag_order) for ch in chunks]
    out = jnp.stack(gathered, axis=1)  # (N, C, per_chunk, ...)
    out = out.reshape((length,) + out.shape[3:])
    return jnp.moveaxis(out, 0, axis) if axis != 0 else out


def perhop_all_reduce(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    rs_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    stage_modes: Optional[Sequence[str]] = None,
) -> jax.Array:
    """Per-hop staged all-reduce: RS then AG sharing one plan (the AG stage
    order is the reverse of the RS order).  Equals ``lax.psum(x,
    tuple(axis_names))`` up to reduction order.

    ``stage_modes`` covers the full 2k-stage chain (RS stages then AG
    stages), matching ``choose_hop_schedule(..., collective="ar")``.
    """
    axis_names = tuple(axis_names)
    order = (
        _check_order(rs_order, axis_names)
        if rs_order is not None
        else tuple(reversed(axis_names))
    )
    k = len(axis_names)
    modes = _resolve_modes(stage_modes, 2 * k)
    y = perhop_reduce_scatter(
        x, axis_names, stage_order=order, axis=axis, stage_modes=modes[:k]
    )
    return perhop_all_gather(
        y, axis_names, stage_order=tuple(reversed(order)), axis=axis,
        stage_modes=modes[k:],
    )
