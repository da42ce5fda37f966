"""Staged collective engine: OpTree's k-stage machinery generalized beyond
all-gather.

``staged_all_gather`` (staged_allgather.py) runs the paper's stages
minor-payload-first so the slow links move the *small* payload.  This module
adds the rest of the gather-shaped family:

  * ``staged_reduce_scatter`` — the exact dual.  A reduce-scatter's payload
    *shrinks* stage by stage, so the paper-optimal order is the **reverse**
    of the all-gather order: the slow (pod/DCN) axes run last, when each
    device holds only the final 1/N shard.  Any stage order composes to the
    canonical (major-first) block layout after one *local* block permutation
    before the scatters — layout work, not communication (the mirror of the
    all-gather's post-transpose).
  * ``staged_all_reduce`` — reduce-scatter + all-gather sharing one plan
    (the AG stage order is the reverse of the RS order).
  * **chunked execution** — every primitive takes ``num_chunks=C``: the
    shard is split into C chunks and stage j of chunk i is issued in the
    same wavefront as stage j+1 of chunk i-1 (SWOT-style software
    pipelining; XLA's scheduler overlaps the independent collectives).  The
    planner (``core.planner.choose_num_chunks``) decides C from the
    alpha/bandwidth trade-off.

The user-facing surface is the context-scoped API (``repro.comms.api``:
``comm_context`` + module ops); ``StagedCollectiveEngine`` and
``tp_all_reduce`` remain as deprecation shims routing through it.
"""
from __future__ import annotations

import math
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from ..core.plan_ir import CollectivePlan
from ..core.planner import (
    LinkSpec,
    choose_hop_schedule,
    plan_axis_order,
    plan_reduce_scatter_order,
)
from .staged_allgather import link_for_axis, names_for_plan, staged_all_gather

__all__ = [
    "staged_reduce_scatter",
    "staged_all_reduce",
    "staged_all_gather_chunked",
    "staged_all_to_all",
    "tp_all_reduce",
    "fit_chunks",
    "plan_collectives",
    "StagedCollectiveEngine",
]


# --------------------------------------------------------------------------
# inside-shard_map primitives
# --------------------------------------------------------------------------

def _check_order(order, axis_names) -> Tuple[str, ...]:
    order = tuple(order)
    if sorted(order) != sorted(axis_names):
        raise ValueError(f"stage_order {order} must permute {axis_names}")
    return order


def _axis_sizes(axis_names: Sequence[str]) -> Dict[str, int]:
    return {n: lax.axis_size(n) for n in axis_names}


def _permute_blocks_to_order(y, axis_names, order, sizes):
    """Local permutation of the N device blocks along dim 0 from canonical
    (major-first ``axis_names``) layout to ``order`` layout, so tiled
    psum_scatter stages executed in ``order`` land each device on its
    canonical block.  Pure layout work — no communication."""
    k = len(axis_names)
    n_total = math.prod(sizes[n] for n in axis_names)
    block = y.shape[0] // n_total
    shaped = y.reshape(tuple(sizes[n] for n in axis_names) + (block,) + y.shape[1:])
    perm = tuple(axis_names.index(n) for n in order)
    shaped = jnp.transpose(shaped, perm + tuple(range(k, shaped.ndim)))
    return shaped.reshape(y.shape)


def _rs_stage(y, name):
    return lax.psum_scatter(y, name, scatter_dimension=0, tiled=True)


def _ag_stage(y, name):
    # stacking form: composes under any stage order; one local fix-up at the
    # end restores canonical device order (cf. staged_all_gather)
    return lax.all_gather(y, name, axis=0, tiled=False)


def _ag_finalize(y, axis_names, order):
    """Collapse the k stacked stage axes (reversed(order) leading) into one
    canonical (N, ...) device axis."""
    k = len(axis_names)
    stacked = tuple(reversed(order))
    perm = tuple(stacked.index(n) for n in axis_names)
    y = jnp.transpose(y, perm + tuple(range(k, y.ndim)))
    n_total = math.prod(y.shape[:k])
    return y.reshape((n_total,) + y.shape[k:])


def _wavefront(chunks: List, num_stages: int, apply_stage) -> List:
    """Software pipeline: at tick t, chunk c runs stage t-c — stage j of
    chunk i is issued alongside stage j+1 of chunk i-1, so independent
    per-chunk collectives can overlap."""
    num_chunks = len(chunks)
    for t in range(num_chunks + num_stages - 1):
        for c in range(num_chunks):
            j = t - c
            if 0 <= j < num_stages:
                chunks[c] = apply_stage(chunks[c], j)
    return chunks


def _split_rs_chunks(y, axis_names, order, sizes, num_chunks):
    """Split the (moveaxis'd) input into num_chunks RS-ready chunks: chunk c
    holds every device block's c-th slice, pre-permuted to ``order`` layout
    when the stage order is non-canonical.  Raises on indivisibility."""
    n_total = math.prod(sizes.values())
    length = y.shape[0]
    if length % (n_total * num_chunks):
        raise ValueError(
            f"axis length {length} not divisible by devices*chunks "
            f"{n_total}*{num_chunks}"
        )

    def prep(chunk):
        if order != axis_names:
            return _permute_blocks_to_order(chunk, axis_names, order, sizes)
        return chunk

    if num_chunks == 1:
        return [prep(y)]
    per_chunk = length // n_total // num_chunks
    blocks = y.reshape((n_total, num_chunks, per_chunk) + y.shape[1:])
    return [
        prep(blocks[:, c].reshape((n_total * per_chunk,) + y.shape[1:]))
        for c in range(num_chunks)
    ]


def staged_reduce_scatter(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    stage_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    num_chunks: int = 1,
) -> jax.Array:
    """k-stage reduce-scatter inside shard_map — the dual of
    ``staged_all_gather``.

    Returns the same value as ``jax.lax.psum_scatter(x, tuple(axis_names),
    scatter_dimension=axis, tiled=True)``: device p (canonical major-first
    order) ends with block p of the sum.

    Args:
      axis_names: factorized sub-axes of the logical axis, *major first*.
      stage_order: execution order (default: paper order — major/slow axis
        **last**, i.e. the slow links carry the smallest payload).
      num_chunks: split the output shard into C chunks and pipeline the
        stages across chunks.
    """
    axis_names = tuple(axis_names)
    order = (
        _check_order(stage_order, axis_names)
        if stage_order is not None
        else tuple(reversed(axis_names))
    )
    sizes = _axis_sizes(axis_names)

    y = jnp.moveaxis(x, axis, 0) if axis != 0 else x
    chunks = _split_rs_chunks(y, axis_names, order, sizes, num_chunks)
    chunks = _wavefront(
        chunks, len(order), lambda ch, j: _rs_stage(ch, order[j])
    )
    out = chunks[0] if num_chunks == 1 else jnp.concatenate(chunks, axis=0)
    return jnp.moveaxis(out, 0, axis) if axis != 0 else out


def staged_all_gather_chunked(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    stage_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    num_chunks: int = 2,
) -> jax.Array:
    """Chunked/pipelined ``staged_all_gather``: equals
    ``lax.all_gather(x, tuple(axis_names), axis=axis, tiled=True)``."""
    axis_names = tuple(axis_names)
    order = (
        _check_order(stage_order, axis_names)
        if stage_order is not None
        else axis_names
    )
    y = jnp.moveaxis(x, axis, 0) if axis != 0 else x
    shard = y.shape[0]
    if shard % num_chunks:
        raise ValueError(f"shard length {shard} not divisible by {num_chunks}")
    per_chunk = shard // num_chunks
    chunks = [y[c * per_chunk:(c + 1) * per_chunk] for c in range(num_chunks)]
    chunks = _wavefront(
        chunks, len(order), lambda ch, j: _ag_stage(ch, order[j])
    )
    gathered = [_ag_finalize(ch, axis_names, order) for ch in chunks]
    # interleave: device p's shard is the concat of its chunks
    out = jnp.stack(gathered, axis=1)  # (N, C, per_chunk, ...)
    n_total = out.shape[0]
    out = out.reshape((n_total * shard,) + out.shape[3:])
    return jnp.moveaxis(out, 0, axis) if axis != 0 else out


def _a2a_split_digits(y, axis_names, sizes):
    """(n_total·B, ...) → (s₁, ..., s_k, B, ...): expose the N destination
    blocks of an all-to-all buffer as one mixed-radix digit axis per sub-axis
    (canonical major-first order), so each stage can transpose its own
    digit independently."""
    n_total = math.prod(sizes[n] for n in axis_names)
    if y.shape[0] % n_total:
        raise ValueError(
            f"axis length {y.shape[0]} not divisible by devices {n_total}"
        )
    block = y.shape[0] // n_total
    return y.reshape(
        tuple(sizes[n] for n in axis_names) + (block,) + y.shape[1:]
    )


def _a2a_merge_digits(y, k: int):
    """Inverse of ``_a2a_split_digits``: collapse the k digit axes + block
    interior back into one (n_total·B, ...) leading axis."""
    n_total = math.prod(y.shape[:k])
    return y.reshape((n_total * y.shape[k],) + y.shape[k + 1:])


def _a2a_stage(y, name, dim):
    # one digit transpose: exchange the m slices along digit axis ``dim``
    # over sub-axis ``name`` (out[d] = device d's slice for us)
    return lax.all_to_all(y, name, split_axis=dim, concat_axis=dim, tiled=True)


def staged_all_to_all(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    stage_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    num_chunks: int = 1,
) -> jax.Array:
    """k-stage all-to-all inside shard_map: equals ``lax.all_to_all(x,
    tuple(axis_names), split_axis=axis, concat_axis=axis, tiled=True)`` bit
    for bit.

    The N-block exchange factorizes into k per-sub-axis digit transposes
    that COMMUTE — any ``stage_order`` yields the identical output and only
    the modeled cost differs (each m-ary stage moves 1/m of every peer's
    shard, never a gathered block).  ``num_chunks=C`` splits the block
    *interior* into C slices and pipelines the stage chain across them in
    the same wavefront as the gather family.
    """
    axis_names = tuple(axis_names)
    order = (
        _check_order(stage_order, axis_names)
        if stage_order is not None
        else axis_names
    )
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
        lambda ch, j: _a2a_stage(ch, order[j], axis_names.index(order[j])),
    )
    out = chunks[0] if num_chunks == 1 else jnp.concatenate(chunks, axis=k)
    out = _a2a_merge_digits(out, k)
    return jnp.moveaxis(out, 0, axis) if axis != 0 else out


def staged_all_reduce(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    rs_order: Optional[Sequence[str]] = None,
    axis: int = 0,
    num_chunks: int = 1,
) -> jax.Array:
    """Staged all-reduce = staged RS + staged AG sharing one plan.

    Equals ``jax.lax.psum(x, tuple(axis_names))``.  The AG stage order is
    the reverse of the RS order, so each payload size crosses each link
    class exactly twice and the slow links only ever carry the scattered
    (smallest) payloads.  With ``num_chunks=C`` the whole 2k-stage RS+AG
    chain is software-pipelined across chunks.
    """
    axis_names = tuple(axis_names)
    order = (
        _check_order(rs_order, axis_names)
        if rs_order is not None
        else tuple(reversed(axis_names))
    )
    ag_order = tuple(reversed(order))
    sizes = _axis_sizes(axis_names)

    y = jnp.moveaxis(x, axis, 0) if axis != 0 else x
    length = y.shape[0]

    if num_chunks == 1:
        out = staged_reduce_scatter(y, axis_names, stage_order=order)
        out = staged_all_gather(out, axis_names, stage_order=ag_order)
        return jnp.moveaxis(out, 0, axis) if axis != 0 else out

    k = len(axis_names)
    chunks = _split_rs_chunks(y, axis_names, order, sizes, num_chunks)

    def apply_stage(ch, j):
        if j < k:
            return _rs_stage(ch, order[j])
        return _ag_stage(ch, ag_order[j - k])

    chunks = _wavefront(chunks, 2 * k, apply_stage)
    gathered = [_ag_finalize(ch, axis_names, ag_order) for ch in chunks]
    out = jnp.stack(gathered, axis=1)  # (N, C, per_chunk, ...)
    out = out.reshape((length,) + out.shape[3:])
    return jnp.moveaxis(out, 0, axis) if axis != 0 else out


def tp_all_reduce(
    x: jax.Array,
    axis_names: Sequence[str],
    *,
    axis: int = -1,
    num_chunks: int = 1,
) -> jax.Array:
    """DEPRECATED shim: tensor-parallel partial-sum combine.

    Use :func:`repro.comms.api.all_reduce` (context-scoped, plan-cached)
    instead; this shim routes through it with the same contract (staged AR
    when divisible, flat ``lax.psum`` fallback otherwise)."""
    import warnings

    from . import api

    warnings.warn(
        "tp_all_reduce is deprecated; use repro.comms.api.all_reduce "
        "under a comm_context", DeprecationWarning, stacklevel=2)
    return api.all_reduce(
        x, axis=axis, axes=tuple(axis_names),
        num_chunks=api.legacy_chunks(num_chunks))


# --------------------------------------------------------------------------
# planning + user-facing engine
# --------------------------------------------------------------------------

def plan_collectives(
    mesh,
    axis_names: Sequence[str],
    shard_bytes: float,
    *,
    links: Optional[Dict[str, LinkSpec]] = None,
    max_chunks: int = 8,
) -> Dict[str, CollectivePlan]:
    """One :class:`~repro.core.plan_ir.CollectivePlan` per collective
    ("ag" / "rs" / "ar" / "a2a") for this (mesh axes, payload) point.

    ``mesh`` is a :class:`jax.sharding.Mesh` or a plain ``{axis: size}``
    dict (the comms context plans from trace-time axis sizes, meshless).
    Stage orders come from the cost-model planners (slow axis first for AG,
    last for RS; the AR chain is the RS order followed by its reverse), the
    execution mode + per-stage hop structure + chunk count from
    ``core.planner.choose_hop_schedule`` — all carried ON the plan, so the
    executor (``comms.plan_executor.execute_plan``), the pricer
    (``core.cost_model.price``) and the optical validator
    (``core.schedule.schedule_from_ir`` → ``optics.simulator``) consume the
    same object.  ``shard_bytes`` is the per-device payload at the
    scattered end (AG input / RS output); for "a2a" it is the node's full
    local exchange buffer (all N destination blocks), matching the IR's
    scaled-payload law (stage j moves shard/f_j)."""
    axis_names = tuple(axis_names)
    if isinstance(mesh, dict):
        sizes = {n: int(mesh[n]) for n in axis_names}
    else:
        sizes = {n: mesh.shape[n] for n in axis_names}
    axes = [(sizes[n], link_for_axis(n, links)) for n in axis_names]
    ag_plan = plan_axis_order(axes, shard_bytes, max_chunks=max_chunks)
    rs_plan = plan_reduce_scatter_order(axes, shard_bytes, max_chunks=max_chunks)
    ag_order = names_for_plan(ag_plan, axis_names, sizes, links)
    rs_order = names_for_plan(rs_plan, axis_names, sizes, links)
    ag_links = [s.link for s in ag_plan.stages]
    rs_links = [s.link for s in rs_plan.stages]
    scheds = {
        "ag": (choose_hop_schedule(
            ag_plan.factors, ag_links, shard_bytes,
            max_chunks=max_chunks, collective="ag"), ag_order),
        "rs": (choose_hop_schedule(
            rs_plan.factors, rs_links, shard_bytes,
            max_chunks=max_chunks, collective="rs"), rs_order),
        "ar": (choose_hop_schedule(
            rs_plan.factors, rs_links, shard_bytes,
            max_chunks=max_chunks, collective="ar"),
            rs_order + tuple(reversed(rs_order))),
        # electrical a2a cost is stage-order invariant (each stage moves
        # shard·(f-1)/f regardless of position), so reuse the AG order as
        # the deterministic choice; order-sensitive optical planning goes
        # through search_stage_orders / PlanPolicy(order="search") instead
        "a2a": (choose_hop_schedule(
            ag_plan.factors, ag_links, shard_bytes,
            max_chunks=max_chunks, collective="a2a"), ag_order),
    }
    plans: Dict[str, CollectivePlan] = {}
    for coll, (sched, order) in scheds.items():
        plan = sched.to_ir(order)
        plans[coll] = dataclasses.replace(
            plan, meta={**plan.meta, "axis_names": axis_names})
    return plans


def fit_chunks(length: int, granularity: int, chunks: int) -> int:
    """Largest power-of-two <= chunks such that length divides into
    granularity*chunks pieces (planner chunk counts are powers of two)."""
    while chunks > 1 and length % (granularity * chunks):
        chunks //= 2
    return chunks


class StagedCollectiveEngine:
    """DEPRECATED shim over the context-scoped API (``repro.comms.api``).

    The engine predates :class:`~repro.comms.api.CommContext`; it now IS
    one — each method delegates to the module-level ops with an explicit
    ``ctx=`` handle, so legacy call sites share the same plan cache,
    policy machinery and links auto-invalidation as the new surface:

        eng = StagedCollectiveEngine(mesh, ("pod", "data"))
        y = eng.all_reduce(x)          # == api.all_reduce(x, ctx=eng.ctx)

    New code should use ``comm_context(mesh, axis_names)`` + the
    ``repro.comms.api`` ops directly.
    """

    def __init__(
        self,
        mesh: Mesh,
        axis_names: Sequence[str],
        *,
        links: Optional[Dict[str, LinkSpec]] = None,
        max_chunks: int = 8,
    ):
        import warnings

        from .api import CommContext, PlanPolicy

        warnings.warn(
            "StagedCollectiveEngine is deprecated; use "
            "repro.comms.api.comm_context(mesh, axis_names) and the "
            "module-level ops", DeprecationWarning, stacklevel=2)
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.max_chunks = max_chunks
        self.n_devices = math.prod(mesh.shape[n] for n in self.axis_names)
        self.ctx = CommContext(
            mesh, self.axis_names, links=links,
            policy=PlanPolicy(max_chunks=max_chunks),
        )

    @property
    def links(self):
        return self.ctx.links

    def plan(self, x: jax.Array, collective: str = "ag") -> CollectivePlan:
        """The CollectivePlan the context would execute for ``x``.

        ``x`` is the full-length array in every case (sharded for AG,
        replicated for RS/AR); the scattered-end payload is nbytes/N."""
        shard_bytes = x.size * x.dtype.itemsize / self.n_devices
        return self.ctx.plan(collective, shard_bytes,
                             shape=tuple(x.shape), dtype=x.dtype)

    def all_gather(
        self, x: jax.Array, *, axis: int = 0, mode: Optional[str] = None
    ) -> jax.Array:
        """x sharded over ``axis_names`` along ``axis`` -> replicated."""
        from . import api

        return api.all_gather(x, axis=axis, ctx=self.ctx, mode=mode)

    def reduce_scatter(
        self, x: jax.Array, *, axis: int = 0, mode: Optional[str] = None
    ) -> jax.Array:
        """x replicated -> summed and scattered over ``axis_names``."""
        from . import api

        return api.reduce_scatter(x, axis=axis, ctx=self.ctx, mode=mode)

    def all_reduce(
        self, x: jax.Array, *, axis: int = 0, mode: Optional[str] = None
    ) -> jax.Array:
        """x replicated -> psum over ``axis_names`` (device count factor)."""
        from . import api

        return api.all_reduce(x, axis=axis, ctx=self.ctx, mode=mode)
