"""Gradient-sync driver: ZeRO-1's reduce-scatter then all-gather of a whole
gradient tree through the program's planned collectives.

Set-up builds the data-parallel mesh of the configuration's chips, makes
every chip's gradient tree from the seed on the device (the reference's
``local_grad``), opens the program's ``comm_context`` on the mesh's data
axes, and compiles the sync: inside ``shard_map``,
``optim.zero1.zero1_shard_grads`` over the data axes and then
``zero1_unshard_params`` of its result, both issued through
``comms.api``.  The window runs syncs back to back in a closed loop, each
ending in ``block_until_ready``.

End-to-end: ``grad_sync_ms``, the window over the syncs completed in it.

``correct``: the last sync's outputs, every leaf, after the reduce-scatter
(each chip's block) and after the all-gather (the whole leaf on each
chip), against the reference's float32 sum of the chips' leaves, remade
on each chip.  The numbers compared are the worst leaf's largest
difference over that leaf's largest reference magnitude.  With the control
(``control.py``) the numbers compared are those of the float8-rounded sum
in the program's place.

With ``--trace 1`` a second traced stretch after the comparison runs the
planned sync and XLA's own ``psum_scatter`` + ``all_gather`` of the same
leaves, alternately, for ``sync_vs_xla``.
"""
from __future__ import annotations

import sys
import time
from pathlib import Path
from typing import Dict

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import flops  # noqa: E402


def mesh_of(run):
    from repro.compat import make_mesh

    dp = run.cell.config["data_parallel"]
    return (make_mesh(tuple(dp["mesh"]), tuple(dp["axes"]),
                      devices=run.device["devices"]), tuple(dp["axes"]))


def make_grads(ref, m: Dict, seed: int, mesh, axes):
    """Every chip's gradient tree: leaf ``i`` as a (chips, *shape) array
    whose row ``k`` lives on chip ``k``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    shapes = ref.leaf_shapes(m)
    dtype = jnp.dtype(m["dtype"])

    def body(seed_arr):
        chip = jax.lax.axis_index(axes)
        return [ref.local_grad(seed_arr[0], i, chip, shape, dtype)[None]
                for i, (_, shape) in enumerate(shapes)]

    make = jax.jit(shard_map(body, mesh=mesh, in_specs=P(),
                             out_specs=P(axes)))
    leaves = make(jnp.asarray([seed % 2**32], jnp.uint32))
    return jax.tree.unflatten(ref.tree_def(m), leaves)


def planned_sync_fn(mesh, axes):
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map
    from repro.optim.zero1 import zero1_shard_grads, zero1_unshard_params

    def planned_sync(grads):
        local = jax.tree.map(lambda g: g[0], grads)
        shards = zero1_shard_grads(local, axes)
        return shards, zero1_unshard_params(shards, axes)

    return jax.jit(shard_map(planned_sync, mesh=mesh, in_specs=P(axes),
                             out_specs=(P(axes), P())))


def xla_sync_fn(mesh, axes):
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    def xla_sync(grads):
        local = jax.tree.map(lambda g: g[0], grads)
        shards = jax.tree.map(
            lambda g: lax.psum_scatter(g, axes, scatter_dimension=0,
                                       tiled=True), local)
        return shards, jax.tree.map(
            lambda s: lax.all_gather(s, axes, axis=0, tiled=True), shards)

    return jax.jit(shard_map(xla_sync, mesh=mesh, in_specs=P(axes),
                             out_specs=(P(axes), P())))


def _per_leaf(ref, m: Dict, seed: int, mesh, axes, body, *leaves):
    """Run ``body(seed, i, shape, *leaf_i)`` on every chip for every leaf
    and return the (leaves, chips, k) array of what it returns."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.compat import shard_map

    seed_arr = jnp.asarray([seed % 2**32], jnp.uint32)
    specs = (P(),) + tuple(P(axes) if j == 0 else P()
                           for j in range(len(leaves)))
    rows = []
    for i, (_, shape) in enumerate(ref.leaf_shapes(m)):
        fn = jax.jit(shard_map(
            lambda s, *xs, i=i, shape=shape: body(s[0], i, shape, *xs)[None],
            mesh=mesh, in_specs=specs[: len(leaves) + 1], out_specs=P(axes)))
        rows.append(np.asarray(fn(seed_arr, *(lv[i] for lv in leaves))))
    return np.stack(rows)


def compare(ref, m: Dict, seed: int, mesh, axes, shards,
            gathered) -> Dict[str, float]:
    """Worst leaf, after the reduce-scatter and after the all-gather, of
    max|program - reference| / max|reference|, each chip remaking the
    reference for its own block and for the whole leaf."""
    import jax
    import jax.numpy as jnp

    chips, dtype = mesh.devices.size, jnp.dtype(m["dtype"])

    def body(seed, i, shape, rs, ag):
        want = ref.leaf_sum(seed, i, chips, shape, dtype)
        scale = jnp.maximum(jnp.max(jnp.abs(want)), 1e-30)
        blk = shape[0] // chips
        mine = jax.lax.dynamic_slice_in_dim(
            want, jax.lax.axis_index(axes) * blk, blk, 0)
        return jnp.stack([
            jnp.max(jnp.abs(rs.astype(jnp.float32) - mine)) / scale,
            jnp.max(jnp.abs(ag.astype(jnp.float32) - want)) / scale])

    gaps = _per_leaf(ref, m, seed, mesh, axes, body,
                     jax.tree.leaves(shards), jax.tree.leaves(gathered))
    return {"rs_gap": float(gaps[..., 0].max()),
            "ag_gap": float(gaps[..., 1].max())}


def control_gap(ref, m: Dict, seed: int, mesh, axes) -> float:
    """The control in the program's place: the sum of the chips' leaves
    rounded to float8 first, against the exact sum (both the block after
    the reduce-scatter and the whole leaf after the all-gather are this
    sum, so one number serves both)."""
    import jax.numpy as jnp

    chips, dtype = mesh.devices.size, jnp.dtype(m["dtype"])

    def body(seed, i, shape):
        want = ref.leaf_sum(seed, i, chips, shape, dtype)
        low = ref.leaf_sum(seed, i, chips, shape, dtype, quant=True)
        scale = jnp.maximum(jnp.max(jnp.abs(want)), 1e-30)
        return jnp.max(jnp.abs(low - want))[None] / scale

    return float(_per_leaf(ref, m, seed, mesh, axes, body).max())


def _halves(leaves):
    """Two groups of whole leaves of about equal bytes."""
    order = sorted(range(len(leaves)), key=lambda i: -leaves[i].nbytes)
    groups, sizes = ([], []), [0, 0]
    for i in order:
        k = 0 if sizes[0] <= sizes[1] else 1
        groups[k].append(i)
        sizes[k] += leaves[i].nbytes
    return groups


def _vs_xla(run, ref, model, mesh, axes, sync) -> None:
    """The traced comparison with XLA's own collectives: planned and XLA
    syncs alternate, each over one half of the tree at a time (XLA's sync
    of the whole tree needs 5 GB more temporaries than the planned one and
    does not fit beside the gradients), the same halves for both."""
    import jax

    leaves = jax.tree.leaves(make_grads(ref, model, run.seed, mesh, axes))
    halves = [[leaves[i] for i in g] for g in _halves(leaves)]
    xla = xla_sync_fn(mesh, axes)
    for h in halves:  # compile both before the traced stretch
        jax.block_until_ready((sync(h), xla(h)))
    with run.side_trace("vs_xla"):
        for _ in range(run.cell.traffic["traced_pairs"]):
            for h in halves:
                jax.block_until_ready(sync(h))
                jax.block_until_ready(xla(h))


def run(run) -> Dict:
    import jax

    from repro.comms import api

    model = run.cell.config["model"]
    ref = run.cell.reference()
    mesh, axes = mesh_of(run)
    chips = mesh.devices.size
    tree_nbytes = flops.tree_bytes((s for _, s in ref.leaf_shapes(model)),
                                   flops.DTYPE_BYTES[model["dtype"]])
    grads = make_grads(ref, model, run.seed, mesh, axes)
    jax.block_until_ready(grads)
    run.mark("gradients")
    with api.comm_context(mesh, axes) as ctx:
        sync = planned_sync_fn(mesh, axes)
        jax.block_until_ready(sync(grads))  # compile and warm
        run.mark("warm-up")
        out = None
        n = 0
        with run.window() as t0:
            end = t0 + run.seconds
            while time.perf_counter() < end:
                out = None  # free the last outputs before the next sync
                with run.spans.span("sync"):
                    out = sync(grads)
                    jax.block_until_ready(out)
                n += 1
        window = run.window_s
        telemetry = ctx.telemetry_snapshot()
        peak = run.memory_peak_bytes()
        del grads
        if run.control:  # the control stands in the program's place
            out = None
            ctrl = control_gap(ref, model, run.seed, mesh, axes)
            got = {"rs_gap": ctrl, "ag_gap": ctrl}
        else:
            got = compare(ref, model, run.seed, mesh, axes, *out)
            out = None
        if run.trace:
            _vs_xla(run, ref, model, mesh, axes, sync)
    lim = run.cell.limits
    checks = {k: {"value": got[k], "limit": lim[k]} for k in ("rs_gap",
                                                              "ag_gap")}
    notes = [f"syncs {n} in {window:.3f} s; tree {tree_nbytes} bytes over "
             f"{chips} chips ({dict(mesh.shape)})",
             f"comm context: {telemetry}"]
    counts = {"syncs": n, "recv_bytes_per_sync":
              flops.sync_recv_bytes(tree_nbytes, chips)}
    result = {"e2e": {"grad_sync_ms": 1e3 * window / max(n, 1)},
              "attempted": n, "failed": 0, "checks": checks,
              "memory_peak_bytes": peak, "counts": counts, "notes": notes}
    return result
