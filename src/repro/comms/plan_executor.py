"""IR-interpreting executor: run one CollectivePlan inside shard_map.

This is consumer (3) of the unified IR (``core.plan_ir``): the JAX engine
no longer re-derives stage orders, chunk counts, or per-stage execution
modes at the callsite — ``execute_plan`` reads them off the plan and maps
its stage chain onto the shard_map primitives:

  * plan mode ``chunked`` → the ``num_chunks``-chunk wavefront over
    blocking whole-stage collectives (``staged_collectives``);
  * plan mode ``hybrid`` → the same chunk wavefront run OVER the per-hop
    stage executors (``ring_executor.hybrid_*``): chunk i's stage j
    overlaps chunk i-1's stage j+1 while every ring stage double-buffers
    its own hops — the perhop-chunked combination the planner emits when
    its modeled makespan beats both pure modes;
  * otherwise → the staged executors of ``ring_executor`` with one
    ``stage_modes`` entry per stage: a stage whose effective IR mode is
    ``perhop`` runs as a double-buffered ppermute ring, the rest as the
    blocking XLA collective (under plan mode ``oneshot`` every stage is
    blocking — ``effective_stage_mode``).

Because the same plan object is priced (``core.cost_model.price``), lowered
to lightpaths (``core.schedule.schedule_from_ir``) and executed here,
planner decisions and executor behavior cannot drift.  Outputs are
bit-identical to the XLA one-shot collectives (AG/RS exactly; AR up to
reduction order) — enforced by ``tests/subproc/check_plan_executor.py``.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..core.plan_ir import CollectivePlan, PlanStage, effective_stage_mode
from ..obs import scope
from .exchange_executor import (
    exchange_all_gather,
    exchange_all_reduce,
    exchange_reduce_scatter,
)
from .ring_executor import (
    hybrid_all_gather,
    hybrid_all_reduce,
    hybrid_all_to_all,
    hybrid_reduce_scatter,
    perhop_all_gather,
    perhop_all_to_all,
    perhop_reduce_scatter,
)
from .staged_collectives import (
    staged_all_gather_chunked,
    staged_all_reduce,
    staged_all_to_all,
    staged_reduce_scatter,
)

__all__ = [
    "execute_plan",
    "execute_plan_verified",
    "oneshot_reference",
    "plan_axis_names",
]


def plan_axis_names(plan: CollectivePlan) -> Tuple[str, ...]:
    """Canonical (major-first mesh order) axis names the plan gathers over,
    stamped into ``plan.meta`` by ``comms.staged_collectives.plan_collectives``."""
    names = plan.meta.get("axis_names")
    if not names:
        raise ValueError(
            "plan has no meta['axis_names']; build engine plans via "
            "plan_collectives (paper-world plans lower through "
            "core.schedule.schedule_from_ir instead)"
        )
    return tuple(names)


def _executor_modes(
    plan: CollectivePlan, stages: Sequence[PlanStage]
) -> Tuple[str, ...]:
    """Per-stage ``ring_executor`` stage_modes ("ring"/"oneshot") for the
    stages' EFFECTIVE hop structure under the plan-level mode."""
    return tuple(
        "ring" if effective_stage_mode(plan, s) == "perhop" else "oneshot"
        for s in stages
    )


def execute_plan(y: jax.Array, plan: CollectivePlan, *, axis: int = 0,
                 stage_probe: Optional[Callable] = None) -> jax.Array:
    """Execute ``plan`` on the local shard ``y`` inside shard_map.

    * ``ag`` — ``y`` is the local shard; returns the full gather (equals
      ``lax.all_gather(y, names, axis=axis, tiled=True)`` bit for bit).
    * ``rs`` — ``y`` is the full-length local addend; returns this device's
      canonical block of the sum (equals ``lax.psum_scatter``).
    * ``ar`` — returns ``lax.psum(y, names)`` (up to reduction order for
      per-hop ring stages).
    * ``a2a`` — ``y`` is the full local exchange buffer (N destination
      blocks along ``axis``); returns the block transpose (equals
      ``lax.all_to_all(y, names, split_axis=axis, concat_axis=axis,
      tiled=True)`` bit for bit).

    ``stage_probe(before, after, name, kind)`` is invoked once per stage on
    the per-hop (non-chunked) paths with the stage's traced input/output
    and the stage's traffic kind ("ag"/"rs"/"a2a") — the hook
    :func:`execute_plan_verified` uses for per-stage checksums.  The
    chunked/hybrid wavefronts do not expose stage boundaries; verification
    there happens at collective granularity.

    It runs under ``scope("plan.<collective>")``, each stage's
    communication under its ``obs.stage_scope``: in a trace, the plan's ops
    outside every stage scope are its local layout work.
    """
    with scope(f"plan.{plan.collective}"):
        return _execute(y, plan, axis, stage_probe)


def _execute(y: jax.Array, plan: CollectivePlan, axis: int,
             stage_probe: Optional[Callable]) -> jax.Array:
    names = plan_axis_names(plan)
    coll = plan.collective
    chunked = plan.mode == "chunked" and plan.num_chunks > 1
    # a one-chunk hybrid degenerates to the per-hop path (same stages, no
    # wavefront) — matching ``CollectivePlan.with_chunks`` normalization
    hybrid = plan.mode == "hybrid" and plan.num_chunks > 1

    def probe_for(kind: str) -> Optional[Callable]:
        if stage_probe is None:
            return None
        return lambda before, after, name: stage_probe(before, after, name, kind)

    if any(s.mode == "exchange" for s in plan.stages):
        # latency-regime plans: recursive-doubling pairwise rounds.  They
        # are single-shot by construction — the planner never chunks them
        # (KiB payloads are under the chunking floor anyway).
        if plan.num_chunks > 1:
            raise ValueError(
                f"exchange (latency) plans execute single-shot, got "
                f"num_chunks={plan.num_chunks}")
        if coll == "ag":
            return exchange_all_gather(
                y, plan, axis=axis, stage_probe=probe_for("ag"))
        if coll == "rs":
            return exchange_reduce_scatter(
                y, plan, axis=axis, stage_probe=probe_for("rs"))
        if coll == "ar":
            return exchange_all_reduce(
                y, plan, axis=axis, rs_probe=probe_for("rs"),
                ag_probe=probe_for("ag"))
        raise ValueError(
            f"exchange stages unsupported for collective {coll!r}")

    if coll == "ag":
        order = plan.axes
        if chunked:
            return staged_all_gather_chunked(
                y, names, stage_order=order, axis=axis,
                num_chunks=plan.num_chunks)
        if hybrid:
            return hybrid_all_gather(
                y, names, stage_order=order, axis=axis,
                num_chunks=plan.num_chunks,
                stage_modes=_executor_modes(plan, plan.stages))
        return perhop_all_gather(
            y, names, stage_order=order, axis=axis,
            stage_modes=_executor_modes(plan, plan.stages),
            stage_probe=probe_for("ag"))

    if coll == "rs":
        order = plan.axes
        if chunked:
            return staged_reduce_scatter(
                y, names, stage_order=order, axis=axis,
                num_chunks=plan.num_chunks)
        if hybrid:
            return hybrid_reduce_scatter(
                y, names, stage_order=order, axis=axis,
                num_chunks=plan.num_chunks,
                stage_modes=_executor_modes(plan, plan.stages))
        return perhop_reduce_scatter(
            y, names, stage_order=order, axis=axis,
            stage_modes=_executor_modes(plan, plan.stages),
            stage_probe=probe_for("rs"))

    if coll == "a2a":
        order = plan.axes
        if chunked:
            return staged_all_to_all(
                y, names, stage_order=order, axis=axis,
                num_chunks=plan.num_chunks)
        if hybrid:
            return hybrid_all_to_all(
                y, names, stage_order=order, axis=axis,
                num_chunks=plan.num_chunks,
                stage_modes=_executor_modes(plan, plan.stages))
        return perhop_all_to_all(
            y, names, stage_order=order, axis=axis,
            stage_modes=_executor_modes(plan, plan.stages),
            stage_probe=probe_for("a2a"))

    if coll == "ar":
        k = len(plan.stages) // 2
        rs_stages, ag_stages = plan.stages[:k], plan.stages[k:]
        rs_order = tuple(st.axis for st in rs_stages)
        if chunked:
            return staged_all_reduce(
                y, names, rs_order=rs_order, axis=axis,
                num_chunks=plan.num_chunks)
        if hybrid:
            return hybrid_all_reduce(
                y, names, rs_order=rs_order, axis=axis,
                num_chunks=plan.num_chunks,
                stage_modes=_executor_modes(plan, plan.stages))
        y = perhop_reduce_scatter(
            y, names, stage_order=rs_order, axis=axis,
            stage_modes=_executor_modes(plan, rs_stages),
            stage_probe=probe_for("rs"))
        return perhop_all_gather(
            y, names, stage_order=tuple(st.axis for st in ag_stages),
            axis=axis, stage_modes=_executor_modes(plan, ag_stages),
            stage_probe=probe_for("ag"), first_stage=k)

    raise ValueError(f"unknown collective {coll!r}")


# --------------------------------------------------------------------------
# verified execution: per-stage checksums, bounded retry, one-shot fallback
# --------------------------------------------------------------------------

def oneshot_reference(y: jax.Array, plan: CollectivePlan, *,
                      axis: int = 0) -> jax.Array:
    """The XLA one-shot collective for ``plan`` — the graceful-degradation
    target: bit-identical to what the staged executor produces on healthy
    hardware (AG/RS/A2A exactly; AR up to reduction order)."""
    names = plan_axis_names(plan)
    coll = plan.collective
    if coll == "ag":
        return lax.all_gather(y, names, axis=axis, tiled=True)
    if coll == "rs":
        return lax.psum_scatter(y, names, scatter_dimension=axis, tiled=True)
    if coll == "ar":
        return lax.psum(y, names)
    if coll == "a2a":
        return lax.all_to_all(y, names, split_axis=axis, concat_axis=axis,
                              tiled=True)
    raise ValueError(f"unknown collective {coll!r}")


def _close(a: jax.Array, b: jax.Array, tol: float) -> jax.Array:
    if tol == 0.0:
        return a == b
    scale = jnp.maximum(jnp.maximum(jnp.abs(a), jnp.abs(b)), 1.0)
    return jnp.abs(a - b) <= tol * scale


def _conservation_ok(y, out, plan, names, tol) -> jax.Array:
    """Whole-collective conservation checksum.  All four collectives
    preserve the group's total mass; AG and AR additionally deliver the
    full total to EVERY device, so their check is per-device (a device
    whose gather lost a block fails locally)."""
    tin = lax.psum(jnp.sum(y), names)
    if plan.collective in ("ag", "ar"):
        return _close(jnp.sum(out), tin, tol)
    return _close(lax.psum(jnp.sum(out), names), tin, tol)


def execute_plan_verified(
    y: jax.Array,
    plan: CollectivePlan,
    *,
    axis: int = 0,
    retries: int = 1,
    tol: float = 0.0,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Checksummed execution with bounded retry and graceful degradation.

    Runs ``plan`` up to ``retries + 1`` times; each attempt is verified by
    per-stage conservation checksums (via the ``stage_probe`` hook on the
    per-hop paths — an AG stage must deliver the stage group's full mass to
    every member, RS/A2A stages must preserve the group total) plus the
    whole-collective checksum.  The result is the FIRST attempt whose every
    checksum passes; if none passes, the bit-identical XLA one-shot
    collective (:func:`oneshot_reference`) is selected instead — degraded
    throughput, never corrupted data.

    ``tol`` is the relative checksum tolerance; the default ``0.0`` demands
    exact equality, which holds for exactly-representable sums (the chaos
    harness uses integer-valued payloads).  Float rounding with ``tol=0``
    can only cause a spurious *fallback*, never a wrong result.

    Returns ``(out, diag)``; ``diag["attempt_ok"]`` is the per-attempt
    verdict vector, ``diag["used_fallback"]`` the scalar bool that no
    attempt survived, and ``diag["stage_ok"]`` (per-hop paths only) the
    (attempt, stage) checksum matrix.  All verification is traced — inside
    jit/shard_map the diagnostics are arrays, not Python bools.
    """
    if retries < 0:
        raise ValueError(f"retries must be >= 0, got {retries}")
    attempts = []
    attempt_oks = []
    stage_ok_rows = []
    for _ in range(retries + 1):
        stage_oks: list = []

        def probe(before, after, name, kind, _oks=stage_oks):
            tin = lax.psum(jnp.sum(before), name)
            if kind == "ag":
                ok = _close(jnp.sum(after), tin, tol)
            else:  # rs / a2a: stage-group total conservation
                ok = _close(lax.psum(jnp.sum(after), name), tin, tol)
            _oks.append(ok)

        out = execute_plan(y, plan, axis=axis, stage_probe=probe)
        ok = _conservation_ok(y, out, plan, plan_axis_names(plan), tol)
        for s_ok in stage_oks:
            ok = jnp.logical_and(ok, s_ok)
        attempts.append(out)
        attempt_oks.append(ok)
        stage_ok_rows.append(stage_oks)
    fallback = oneshot_reference(y, plan, axis=axis)
    out = fallback
    for a_out, a_ok in reversed(list(zip(attempts, attempt_oks))):
        out = jnp.where(a_ok, a_out, out)
    any_ok = attempt_oks[0]
    for a_ok in attempt_oks[1:]:
        any_ok = jnp.logical_or(any_ok, a_ok)
    diag: Dict[str, jax.Array] = {
        "attempt_ok": jnp.stack(attempt_oks),
        "used_fallback": jnp.logical_not(any_ok),
    }
    if stage_ok_rows[0]:
        diag["stage_ok"] = jnp.stack(
            [jnp.stack(row) for row in stage_ok_rows])
    return out, diag
