"""Cluster training driver: mesh + pjit + ZeRO-1 + fault-tolerant loop.

On a real TPU cluster this runs under `jax.distributed.initialize()` with
one process per host; offline it can be exercised with fake host devices:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
      --reduced --mesh 2,4 --steps 10

Production invocation (per the assignment's mesh):
  python -m repro.launch.train --arch qwen3-32b --mesh 16,16 --steps 500
"""
import argparse
import contextlib
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.comms import comm_context
from repro.configs import (
    SHAPES,
    expert_parallel,
    get_config,
    reduced as reduce_cfg,
)
from repro.data import DataConfig, SyntheticLMPipeline
from repro.launch.device import (device_info, place_compile_cache,
                                 select_kernel_backend)
from repro.models import init_params, loss_fn
from repro.models import sharding as shd
from repro.optim import OptimizerConfig, adamw_init, adamw_update, opt_state_specs
from repro.optim.zero1 import zero1_shard_grads, zero1_unshard_params
from repro.checkpoint import Checkpointer


def comm_plan_telemetry(ctx) -> list:
    """Per-plan telemetry lines for one CommContext: the cache counters
    (hits / misses / invalidated) and, per cached CollectivePlan, the
    collective, payload, chosen execution mode/chunks, the stage order it
    executes, how often it was issued, and — when the policy ran the
    cross-world order search — which backend picked the order and whether
    it flipped vs the other world.  Emitted every ``--log-every`` steps by
    the explicit train loop (not just at exit), so a mid-run links update
    (auto-calibration) is visible as invalidations + re-planned orders."""
    snap = ctx.telemetry_snapshot()
    st = snap["cache"]
    lines = [f"comm plans={snap['plans']} hits={st['hits']} "
             f"misses={st['misses']} invalidated={st['invalidated']} "
             f"replans_on_fault={st['replans_on_fault']} "
             f"fallbacks={st['fallbacks']} "
             f"latency_plans={st['latency_plans']} "
             f"ring_plans={st['ring_plans']} "
             f"health={snap['health_fp']}"]
    if ctx.axis_names:
        xover = snap["crossover_ar_bytes"]
        lines.append(
            f"  regime crossover(ar): "
            f"{'n/a' if xover is None else format(xover, '.0f') + 'B'} — "
            f"payloads below it plan recursive-doubling exchange chains")
    for rec in snap["per_plan"]:
        order = ",".join(rec["order"])
        line = (f"  {rec['collective']} "
                f"shard={rec['shard_bytes'] / 2**10:.1f}KiB "
                f"regime={rec['regime']} "
                f"mode={rec['mode']} chunks={rec['num_chunks']} "
                f"order=[{order}] issued=x{rec['issued']}")
        srch = rec.get("order_search")
        if srch:
            line += (f" picked_by={srch['backend']}"
                     f" flipped={srch['flipped']}"
                     f" regime_flipped={srch['regime_flipped']}"
                     f" reconfigs={srch.get('reconfigurations', 0)}")
        if rec.get("fallback"):
            line += " degraded=oneshot-fallback"
        lines.append(line)
    return lines


def modeled_pod_traffic_note(grad_bytes: float, mesh) -> str:
    """Modeled per-device pod(DCN)-axis gradient-sync traffic per step.

    Spec-based path: GSPMD's flat all-reduce over all data axes moves the
    full gradient over every axis, pod included — 2·G·(pod-1)/pod per device
    (RS+AG halves of the ring).  Explicit ZeRO-1 path
    (``zero1_shard_grads``): the pod axis is reduced on the already
    data-scattered shard, so it carries only G/data of that.
    """
    pod = mesh.shape.get("pod", 1)
    if pod == 1:
        return "pod-axis traffic: n/a (no pod axis in this mesh)"
    data = mesh.shape["data"]
    spec_mb = 2 * grad_bytes * (pod - 1) / pod / 2**20
    expl_mb = spec_mb / data
    return (f"modeled pod-axis traffic/device: spec={spec_mb:.2f}MiB/step "
            f"explicit={expl_mb:.2f}MiB/step ({data:.0f}x less: pod reduces "
            f"the data-scattered shard)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--mesh", default="16,16", help="data,model axis sizes")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-interval", type=int, default=50)
    ap.add_argument("--resume", action="store_true",
                    help="restore params/opt from the latest committed "
                         "checkpoint in --ckpt-dir and continue from the "
                         "following step (no-op when the dir is empty)")
    ap.add_argument("--fault-step", type=int, default=None,
                    help="chaos hook: at this step, report a link fault to "
                         "the comm context (needs --zero1 explicit); the "
                         "context re-plans its cached collectives in place "
                         "under the degraded world")
    ap.add_argument("--fault-axis", default="data",
                    help="mesh axis the injected fault degrades")
    ap.add_argument("--fault-derate", type=float, default=0.5,
                    help="surviving bandwidth fraction for --fault-step")
    ap.add_argument("--verify-collectives", action="store_true",
                    help="run explicit collectives through the verified "
                         "executor (per-stage checksums + bounded retry + "
                         "one-shot fallback; needs --zero1 explicit)")
    ap.add_argument("--log-every", type=int, default=10,
                    help="step-log interval; with --zero1 explicit each log "
                         "also prints the comm context's per-plan telemetry "
                         "(cache stats + chosen order per plan)")
    ap.add_argument("--expert-parallel", action="store_true",
                    help="MoE archs: shard the experts over the 'data' mesh "
                         "axis and route dispatch/combine through the "
                         "context-planned api.all_to_all (models.moe EP "
                         "path).  Requires --zero1 explicit — the EP "
                         "all-to-all only activates inside the shard_map "
                         "train step where the axis is bound; the a2a "
                         "plans show up in the per-plan comm telemetry.")
    ap.add_argument("--zero1", choices=["spec", "explicit"], default="spec",
                    help="gradient sync: 'spec' lets GSPMD emit the "
                         "collectives from the ZeRO-1 sharding specs; "
                         "'explicit' runs the staged shard_map path "
                         "(zero1_shard_grads: reduce-scatter over data, pod "
                         "reduced on the scattered shard, staged re-gather). "
                         "Explicit is the pure-DP path (model axis must be 1).")
    args = ap.parse_args()

    cache = place_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)
        cfg = dataclasses.replace(cfg, dtype="float32")
    if args.expert_parallel:
        if args.zero1 != "explicit":
            raise SystemExit("--expert-parallel needs --zero1 explicit: the "
                             "EP all-to-all only runs inside the shard_map "
                             "train step where the expert axis is bound")
        cfg = expert_parallel(cfg, axis="data")  # raises if arch has no MoE
    shape = SHAPES["train_4k"]
    seq = args.seq or (64 if args.reduced else shape.seq_len)
    batch = args.batch or (4 if args.reduced else shape.global_batch)

    dims = tuple(int(x) for x in args.mesh.split(","))
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    mesh = compat.make_mesh(dims, names)
    explicit = args.zero1 == "explicit"
    backend = select_kernel_backend(None if explicit else mesh)
    print(f"mesh={dict(mesh.shape)} device={device_info()} "
          f"kernels={backend} compile_cache={cache}")

    if explicit and mesh.shape.get("model", 1) != 1:
        raise SystemExit("--zero1 explicit is the pure-DP shard_map path; "
                         "use a mesh with model axis 1")
    # explicit mode runs the model inside shard_map (manual axes): GSPMD
    # activation constraints don't apply there
    shd.set_activation_policy(None if explicit else
                              {"dp": shd.dp_axes(mesh), "tp": "model",
                               "sequence_parallel": not args.reduced})

    params = init_params(jax.random.key(0), cfg)
    opt_state = adamw_init(params)
    pspecs = shd.sanitize_tree(shd.param_specs(cfg, params), params, mesh)
    ospecs = shd.sanitize_tree(
        opt_state_specs(pspecs, params, mesh), opt_state, mesh
    )
    if explicit:
        p_shard = o_shard = NamedSharding(mesh, P())
    else:
        p_shard = shd.named(mesh, pspecs)
        o_shard = shd.named(mesh, ospecs)
    params = jax.device_put(params, p_shard)
    opt_state = jax.device_put(opt_state, o_shard)

    opt_cfg = OptimizerConfig(warmup_steps=min(20, args.steps // 5 + 1),
                              decay_steps=args.steps)

    dp = shd.dp_axes(mesh)
    dp_divides = batch % np.prod([mesh.shape[a] for a in dp]) == 0
    bspec = NamedSharding(mesh, P(dp, None)) if dp_divides \
        else NamedSharding(mesh, P())

    comm_scope = contextlib.ExitStack()
    ctx = None
    if explicit:
        if not dp_divides:
            raise SystemExit(f"--zero1 explicit needs batch {batch} divisible "
                             f"by the data axes {dp}")
        fast = ("data",)
        slow = ("pod",) if "pod" in mesh.shape else ()
        ndp = int(np.prod([mesh.shape[a] for a in fast + slow]))
        # one context scopes every explicit collective (zero1_shard_grads /
        # zero1_unshard_params resolve it at trace time): plans are cached
        # here, and a fitted --links file or a reported fault re-plans them
        # in place
        pol_kw = {"verify": True} if args.verify_collectives else {}
        ctx = comm_scope.enter_context(comm_context(mesh, fast, **pol_kw))

        def explicit_step(params, opt_state, batch):
            # local grads on the local batch shard; the global mean-loss
            # gradient is (1/ndp)·Σ_ranks local, realized by the staged
            # reduce-scatter below (pod only ever sees the scattered shard)
            (_, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
            grads = jax.tree.map(lambda g: g / ndp, grads)
            grads = zero1_shard_grads(grads, fast, slow)
            grads = zero1_unshard_params(grads, fast, reference=params)
            new_p, new_o = adamw_update(grads, opt_state, params, opt_cfg)
            loss = jax.lax.psum(metrics["loss"], fast + slow) / ndp
            return new_p, new_o, loss

        train_step = jax.jit(compat.shard_map(
            explicit_step, mesh=mesh,
            in_specs=(P(), P(), P(dp, None)),
            out_specs=(P(), P(), P()),
        ))
        grad_bytes = sum(l.size * l.dtype.itemsize
                         for l in jax.tree.leaves(params))
        traffic_note = modeled_pod_traffic_note(grad_bytes, mesh)
        print(f"[train/zero1-explicit] {traffic_note}")
    else:
        traffic_note = ""

        @jax.jit
        def train_step(params, opt_state, batch):
            (_, metrics), grads = jax.value_and_grad(
                lambda p: loss_fn(cfg, p, batch), has_aux=True)(params)
            new_p, new_o = adamw_update(grads, opt_state, params, opt_cfg)
            return new_p, new_o, metrics["loss"]

    if args.fault_step is not None and not explicit:
        raise SystemExit("--fault-step reports into the comm context; it "
                         "needs --zero1 explicit")

    pipe = SyntheticLMPipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=batch)).start()
    ckpt = Checkpointer(args.ckpt_dir)

    start_step = 0
    if args.resume:
        latest = ckpt.latest_step()
        if latest is None:
            print(f"[train/resume] no committed checkpoint in "
                  f"{args.ckpt_dir}; starting fresh")
        else:
            _, state = ckpt.restore({"params": params, "opt": opt_state})
            params = jax.device_put(state["params"], p_shard)
            opt_state = jax.device_put(state["opt"], o_shard)
            start_step = latest + 1
            print(f"[train/resume] resumed from step {latest} "
                  f"(next step {start_step})")

    t0 = time.time()
    loss0 = None
    loss = jnp.nan
    with comm_scope, mesh:
        for step in range(start_step, args.steps):
            if (ctx is not None and args.fault_step is not None
                    and step == args.fault_step):
                ctx.report_fault(axis=args.fault_axis,
                                 derate=args.fault_derate)
                st = ctx.cache_stats
                print(f"[train/fault] step {step}: derate "
                      f"{args.fault_derate} on axis {args.fault_axis!r} -> "
                      f"health={ctx.health_fp} "
                      f"replans_on_fault={st.replans_on_fault} "
                      f"fallbacks={st.fallbacks}")
            raw = next(pipe)
            batch_dev = {k: jax.device_put(jnp.asarray(v), bspec)
                         for k, v in raw.items()}
            params, opt_state, loss = train_step(params, opt_state, batch_dev)
            if step % args.log_every == 0 or step == args.steps - 1:
                lv = float(loss)
                loss0 = lv if loss0 is None else loss0
                extra = f" [{traffic_note}]" if traffic_note else ""
                print(f"step {step:5d} loss {lv:.4f} "
                      f"({(time.time()-t0)/(step-start_step+1):.2f}s/step)"
                      f"{extra}")
                if ctx is not None:
                    for line in comm_plan_telemetry(ctx):
                        print(f"[train/comms] {line}")
            if step and step % args.ckpt_interval == 0:
                ckpt.save(step, {"params": params, "opt": opt_state},
                          blocking=False)
    ckpt.wait()
    pipe.stop()
    if ctx is not None:
        print("[train/zero1-explicit] final comm telemetry:")
        for line in comm_plan_telemetry(ctx):
            print(f"[train/comms] {line}")
        # the same data as ONE structured blob (machine-readable twin of
        # the lines above; the cluster front end logs the same shape)
        print("[train/comms-json] "
              + json.dumps(ctx.telemetry_snapshot(), sort_keys=True))
    if loss0 is None:  # resumed at/past --steps: nothing left to run
        print(f"done: no steps to run (resumed at {start_step} "
              f"of {args.steps})")
    else:
        print(f"done: {args.steps} steps in {time.time()-t0:.1f}s; "
              f"loss {loss0:.4f} -> {float(loss):.4f}")


if __name__ == "__main__":
    main()
