"""Cluster serving driver: batched continuous decode on a mesh.

Offline smoke (single server):
  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
      --requests 5

Multi-replica cluster front end (ISSUE 9):
  PYTHONPATH=src python -m repro.launch.serve --arch granite-3-2b --reduced \
      --replicas 2 --policy greedy --trace poisson:20 --requests 8 --hetero

With ``--replicas N`` the driver builds N ``BatchedServer`` replicas
(``--hetero`` makes odd replicas structurally deeper — the heterogeneous
mesh the routing policies exist for), calibrates each via
``measure_replica_times``, replays the seeded ``--trace`` through BOTH the
event-driven simulator and the live :class:`~repro.cluster.ClusterServer`,
and prints the two drain reports side by side — the simulated-vs-measured
comparison that validates the simulator (see ``docs/serving.md``).

The whole serve loop runs inside ONE ``comm_context`` over the local
devices (axis ``"tp"``): any decode collective — in particular the
sharded-KV combine (``comms/decode_attention.py``), which routes its psums
through ``repro.comms.api.all_reduce`` — plans through this context and
hits its plan cache instead of re-deriving stage orders per trace.  The
cache/plan telemetry is reported when the server drains (including the
same ``telemetry_snapshot()`` JSON blob train.py logs); the reduced
single-device smoke decodes unsharded (0 plans, and the report says so) —
the sharded combine's cache behavior is pinned by
``tests/subproc/check_comms.py`` on an 8-device mesh.
"""
import argparse
import dataclasses
import json
import time

import jax
import numpy as np

from repro.cluster import (ClusterServer, ClusterSim, ReplicaSpec,
                           make_policy, make_trace, measure_replica_times)
from repro.comms import comm_context
from repro.compat import make_mesh
from repro.configs import get_config, reduced as reduce_cfg
from repro.kernels import ops
from repro.launch.device import (device_info, place_compile_cache,
                                 select_kernel_backend)
from repro.models import init_params
from repro.runtime import BatchedServer, ServerConfig


def _comms_report(ctx):
    n_plans = len(ctx.plans())
    note = ("" if n_plans else
            " — none issued: this run's decode path is unsharded; plans "
            "appear when the KV cache shards across devices "
            "(sharded_decode_attention)")
    print(f"[serve/comms] plan cache: {n_plans} plans, "
          f"{ctx.cache_stats}{note}")
    xover = ctx.latency_crossover("ar")
    print(f"[serve/comms] regimes: latency={ctx.cache_stats.latency_plans} "
          f"ring={ctx.cache_stats.ring_plans} crossover(ar)="
          f"{'n/a' if xover is None else format(xover, '.0f') + 'B'} — "
          f"decode psums below the crossover run recursive-doubling "
          f"exchange plans")
    print(f"[serve/comms] health={ctx.health_fp} "
          f"replans_on_fault={ctx.cache_stats.replans_on_fault} "
          f"fallbacks={ctx.cache_stats.fallbacks}")
    print("[serve/comms-json] " + json.dumps(ctx.telemetry_snapshot(),
                                             sort_keys=True))


def serve_prompts(server: BatchedServer, prompts):
    """Drain ``prompts`` through ``server`` inside ONE ``comm_context`` over
    the local devices (axis ``"tp"``).  Returns the results, the host
    seconds the drain took, and the context (for its plan report)."""
    mesh = make_mesh((len(jax.devices()),), ("tp",))
    with comm_context(mesh, ("tp",)) as ctx:
        for p in prompts:
            server.submit(p)
        t0 = time.perf_counter()
        results = server.run_until_drained()
        dt = time.perf_counter() - t0
    return results, dt, ctx


def report_serving(server: BatchedServer, results, dt: float, ctx) -> None:
    toks = sum(len(v) for v in results.values())
    print(f"served {len(results)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s, host clock)")
    print(f"[serve/kernels] backend={ops.get_backend()} "
          f"decode attention: {ops.DECODE_ATTENTION_PATH}")
    rep = server.drain_report()
    print(f"[serve/drain] requests={rep['requests']} tokens={rep['tokens']} "
          f"p50={rep['latency_p50_s'] * 1e3:.2f}ms "
          f"p99={rep['latency_p99_s'] * 1e3:.2f}ms "
          f"ttft_p50={rep['ttft_p50_s'] * 1e3:.2f}ms")
    print(f"[serve/drain] stats={json.dumps(server.stats.to_json())}")
    for r in rep["per_request"]:
        print(f"[serve/drain]   rid={r['rid']} prompt={r['prompt_tokens']} "
              f"gen={r['generated']} queue→prefill→decode→finish "
              f"timestamps recorded")
    _comms_report(ctx)


def _serve_single(args, cfg):
    params = init_params(jax.random.key(0), cfg)
    server = BatchedServer(cfg, params, ServerConfig(
        batch_size=args.batch_size, max_seq=args.max_seq,
        max_new_tokens=args.new_tokens))
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(rng.integers(4, 20)))
               for _ in range(args.requests)]
    report_serving(server, *serve_prompts(server, prompts))


def _serve_cluster(args, cfg):
    cfgs = []
    for i in range(args.replicas):
        c = cfg
        if args.hetero and i % 2 == 1:
            c = dataclasses.replace(
                cfg, num_layers=cfg.num_layers * args.hetero_factor)
        cfgs.append(c)
    scfg = ServerConfig(batch_size=args.batch_size, max_seq=args.max_seq,
                        max_new_tokens=args.new_tokens)
    specs, servers = [], []
    for i, c in enumerate(cfgs):
        params = init_params(jax.random.key(i), c)
        pf, ds = measure_replica_times(c, params, scfg, prompt_tokens=8)
        name = f"r{i}" + ("-deep" if c is not cfg else "")
        print(f"[serve/cluster] {name}: layers={c.num_layers} "
              f"prefill={pf * 1e3:.3f}ms/tok decode={ds * 1e3:.3f}ms/step")
        specs.append(ReplicaSpec.from_times(
            name, scfg.batch_size, prefill_token_s=pf, decode_step_s=ds))
        servers.append(BatchedServer(c, params, scfg))

    trace = make_trace(args.trace, n=args.requests, seed=args.seed,
                       prompt_tokens=(8, 8),
                       new_tokens=(args.new_tokens, args.new_tokens))
    sim = ClusterSim(specs, make_policy(args.policy), world=args.world)
    sim_stats = sim.run(trace)
    print(f"[serve/cluster] simulated({args.policy}) {sim_stats.summary()}")

    # warm each replica's jits so measured timestamps exclude compiles
    for srv in servers:
        srv.submit(np.arange(8, dtype=np.int32) % cfg.vocab_size)
        srv.run_until_drained()
        srv.reset()

    mesh = make_mesh((len(jax.devices()),), ("tp",))
    with comm_context(mesh, ("tp",)) as ctx:
        cluster = ClusterServer(servers, specs, make_policy(args.policy),
                                world=args.world)
        rng = np.random.default_rng(args.seed)
        prompts = [rng.integers(0, cfg.vocab_size, size=r.prompt_tokens)
                   for r in trace]
        meas = cluster.run_trace(trace, prompts=prompts)
    print(f"[serve/cluster] measured({args.policy})  {meas.summary()}")
    print("[serve/cluster-json] " + json.dumps(
        {"policy": args.policy, "world": args.world,
         "trace": args.trace, "seed": args.seed,
         "simulated": sim_stats.to_json(), "measured": meas.to_json()},
        sort_keys=True))
    _comms_report(ctx)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through N BatchedServer replicas behind "
                         "--policy (1: classic single-server path)")
    ap.add_argument("--policy", default="greedy",
                    help="routing policy: round-robin|jsq|greedy|max-flow")
    ap.add_argument("--trace", default="poisson:20",
                    help="arrival trace: poisson:RATE | bursty:RATE[,B] | "
                         "path to a recorded JSON trace")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--world", default="electrical",
                    choices=["electrical", "optical"],
                    help="transmission cost world for routing/simulation")
    ap.add_argument("--hetero", action="store_true",
                    help="make odd replicas deeper (heterogeneous mesh)")
    ap.add_argument("--hetero-factor", type=int, default=8,
                    help="layer multiplier for deep replicas under --hetero")
    args = ap.parse_args()

    cache = place_compile_cache()
    backend = select_kernel_backend()
    print(f"[serve/device] {device_info()} kernels={backend} "
          f"compile_cache={cache}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(reduce_cfg(cfg), dtype="float32")
    if cfg.is_encoder_only:
        raise SystemExit(f"{cfg.name} is encoder-only: no autoregressive serve")

    if args.replicas > 1:
        _serve_cluster(args, cfg)
    else:
        _serve_single(args, cfg)


if __name__ == "__main__":
    main()
