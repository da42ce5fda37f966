#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU.

    python chip_smoke.py               # one chip: serve granite-3-2b
    python chip_smoke.py --four-chips  # four chips: planned collectives

One chip: granite-3-2b at its published widths (40 layers, d_model 2048,
32/8 heads, d_ff 8192, vocab 49155, tied embeddings, bf16) with random
weights from ``--seed``, served through ``launch/serve.py``'s path
(``BatchedServer`` inside one ``comm_context``) with the compiled Pallas
kernels.  Eight requests of two prompt lengths on four slots decode at
mixed positions.  Every request's prefill and per-step decode logits must
match a no-cache ``forward`` of that request alone on the ``ref`` kernels
with the same parameters, and the greedy tokens must agree.

Four chips (``--four-chips``): only the multi-chip path.  ``api.all_gather``,
``reduce_scatter``, ``all_reduce`` and ``all_to_all`` under ``comm_context``
(default policy) on a (4,) and a (2, 2) mesh, at 1 KiB and 1 MiB per-device
shards, must be bit-identical on integer-valued float32 to XLA's own
collectives; the explicit-TP block of one granite-width layer must match
the GSPMD block; every input and output must span all four devices.

Every time printed is a host-clock smoke timing, not a benchmark metric.
The script exits nonzero, and prints no result line, when JAX finds no TPU,
when the repository's ``src/`` is not beside it, or when any check fails.
On success the last line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

#: prefill and decode logits against the no-cache reference, in units of
#: the reference logits' largest magnitude.  Both run in bf16 (8 mantissa
#: bits), fused differently: batch-4 prefill and cached K/V against one
#: batch-1 pass.  On the CPU at full width this rounding noise measured
#: 1.2e-2 at 2 layers, 1.7e-2 at 4 and 2.5e-2 at 8, growing about as the
#: square root of depth (~6e-2 projected at 40); a server that corrupts
#: other slots' caches measured 0.5-0.6 at 2 layers.
LOGITS_RTOL = 0.1
#: explicit-TP block vs the GSPMD block, float32 at "highest" matmul
#: precision: the two sum the same products in another order (2.9e-7 on
#: the CPU).  The TPU runs float32 "highest" matmuls as several bf16 passes
#: and has its own transcendental units, hence the margin.
TP_BLOCK_RTOL = 1e-4


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def _require_tpu(count: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: JAX found platform "
            f"{devices[0].platform!r}; this smoke runs only on a TPU")
    if len(devices) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX found "
                         f"{len(devices)}")
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: the repository's src/ is not beside "
                         f"this script ({SRC})")
    sys.path.insert(0, str(SRC))


def error_to_scale(got, want) -> float:
    """max |got - want| over the largest |want|."""
    import numpy as np

    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


# --------------------------------------------------------------------------
# one chip: serving
# --------------------------------------------------------------------------
def prefill_compile_seconds(server, prompt_len: int):
    """Compile the server's prefill program, then again after
    ``jax.clear_caches()``.  The second compile in this process is served by
    the persistent compile cache; a process started after this one finds
    the first compile there too.  Returns (first_s, again_s, has_kernel)."""
    import jax
    import jax.numpy as jnp

    def compile_once():
        toks = jnp.zeros((server.scfg.batch_size, prompt_len), jnp.int32)
        lowered = server._prefill.lower(server.params, {"tokens": toks},
                                        server.state, server._lanes([0]))
        t0 = time.perf_counter()
        compiled = lowered.compile()
        return time.perf_counter() - t0, compiled

    first_s, compiled = compile_once()
    has_kernel = "tpu_custom_call" in compiled.as_text()
    jax.clear_caches()
    again_s, _ = compile_once()
    return first_s, again_s, has_kernel


def serve_and_check(cfg, *, seed: int, prompt_lens, n_requests: int,
                    batch: int, new_tokens: int, max_seq: int) -> dict:
    """Serve ``n_requests`` random prompts through the serve path and check
    each request's logits against a no-cache reference forward."""
    import jax
    import numpy as np

    from repro.configs.base import param_count
    from repro.kernels import ops
    from repro.launch.serve import report_serving, serve_prompts
    from repro.models import forward, init_params
    from repro.runtime import BatchedServer, ServerConfig

    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=1)(jax.random.key(seed), cfg)
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    jax.block_until_ready(params)
    print(f"[smoke/model] {cfg.name}: layers={cfg.num_layers} "
          f"d_model={cfg.d_model} heads={cfg.num_heads}/{cfg.num_kv_heads} "
          f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"params={n_params} (analytic {param_count(cfg)}) "
          f"dtype={cfg.dtype} init {time.perf_counter() - t0:.2f}s")

    server = BatchedServer(cfg, params, ServerConfig(
        batch_size=batch, max_seq=max_seq, max_new_tokens=new_tokens,
        keep_logits=True))
    first_s, again_s, has_kernel = prefill_compile_seconds(
        server, prompt_lens[0])
    print(f"[smoke/compile] prefill (batch {batch}, {prompt_lens[0]} tokens),"
          f" host clock: first compile in this process {first_s:.2f}s "
          f"(cold unless an earlier process filled the persistent cache), "
          f"again after jax.clear_caches() {again_s:.2f}s (a persistent-cache"
          f" hit within this process); tpu_custom_call in compiled prefill: "
          f"{has_kernel}")

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=prompt_lens[i % len(prompt_lens)]
                            ).astype(np.int32) for i in range(n_requests)]
    results, dt, ctx = serve_prompts(server, prompts)
    report_serving(server, results, dt, ctx)

    # the reference: each request alone, no cache, on the jnp kernels
    with ops.backend_scope("ref"):
        ref_fn = jax.jit(lambda p, t: forward(cfg, p, {"tokens": t})[0])
        worst, agree, ties = 0.0, True, 0
        for rid, prompt in enumerate(prompts):
            gen = results[rid]
            toks = np.concatenate([prompt, np.asarray(gen[:-1], np.int32)])
            want = np.asarray(ref_fn(params, toks[None])[0, len(prompt) - 1:],
                              np.float32)
            got = np.stack(server.logits[rid])
            if got.shape != want.shape:
                _fail(f"rid {rid}: logits {got.shape} vs reference "
                      f"{want.shape}")
            if not np.all(np.isfinite(got)):
                _fail(f"rid {rid}: non-finite logits")
            err = error_to_scale(got, want)
            # the greedy token must be the reference's argmax wherever the
            # reference's top two differ by more than twice the logits'
            # measured error (closer, either may win)
            top2 = np.sort(want, axis=-1)[:, -2:]
            decided = top2[:, 1] - top2[:, 0] > 2 * np.abs(got - want).max()
            same = bool(np.array_equal(np.argmax(want, -1)[decided],
                                       np.asarray(gen)[decided]))
            worst, agree = max(worst, err), agree and same
            ties += int(np.sum(~decided))
            print(f"[smoke/check] rid={rid} prompt={len(prompt)} "
                  f"steps={len(gen)} logits err/scale={err:.3e} "
                  f"argmax agree={same} near-ties={int(np.sum(~decided))}")
    print(f"[smoke/check] logits: worst max|server - reference| / "
          f"max|reference| = {worst:.3e} (limit {LOGITS_RTOL:.1e}); argmax "
          f"agree: "
          f"{agree} ({ties} steps whose reference top two lie within twice "
          f"the error are not compared)")
    return {"has_kernel": has_kernel, "logits_err": worst,
            "argmax_agree": agree}


def one_chip(seed: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.kernels import ops

    cfg = get_config("granite-3-2b")
    out = serve_and_check(cfg, seed=seed, prompt_lens=(48, 200),
                          n_requests=8, batch=4, new_tokens=16, max_seq=256)
    stats = jax.devices()[0].memory_stats() or {}
    print(f"[smoke/memory] peak_bytes_in_use="
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(f"[smoke/kernels] backend={ops.get_backend()} interpret="
          f"{ops._INTERPRET}; decode attention: {ops.DECODE_ATTENTION_PATH}")
    if not out["has_kernel"]:
        _fail("no tpu_custom_call in the compiled prefill: the Pallas "
              "kernels did not compile into it")
    if out["logits_err"] > LOGITS_RTOL:
        _fail(f"logits err {out['logits_err']:.3e} > {LOGITS_RTOL:.1e}")
    if not out["argmax_agree"]:
        _fail("greedy tokens differ from the reference's argmax")


# --------------------------------------------------------------------------
# four chips: planned collectives and the explicit-TP block
# --------------------------------------------------------------------------
def check_collectives(mesh, names, shard_bytes: int) -> None:
    """The four context-planned collectives against XLA's, inside one
    shard_map, on integer-valued float32 (sums are exact, so equal bits)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.comms import api
    from repro.compat import shard_map

    n = mesh.devices.size
    per = shard_bytes // 4
    x = (jnp.arange(n * per, dtype=jnp.int32) % 251 - 125).astype(jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P(names)))

    def body(y):
        return (api.all_gather(y), lax.all_gather(y, names, tiled=True),
                api.reduce_scatter(y), lax.psum_scatter(y, names, tiled=True),
                api.all_reduce(y, axis=0), lax.psum(y, names),
                api.all_to_all(y), lax.all_to_all(y, names, 0, 0, tiled=True))

    with api.comm_context(mesh, names) as ctx:
        outs = jax.jit(shard_map(body, mesh=mesh, in_specs=P(names),
                                 out_specs=(P(names),) * 8))(x)
    spans = [len(a.sharding.device_set) for a in (x,) + tuple(outs)]
    for op, got, want in zip(("ag", "rs", "ar", "a2a"), outs[::2], outs[1::2]):
        same = bool(np.array_equal(np.asarray(got), np.asarray(want)))
        plan = next(p for p in ctx.plans() if p.collective == op)
        print(f"[smoke/collectives] mesh={dict(mesh.shape)} {op} "
              f"shard={shard_bytes}B plan={plan.meta.get('regime', '?')}/"
              f"{plan.mode} bit-identical to XLA: {same}")
        if not same:
            _fail(f"{op} on {dict(mesh.shape)} at {shard_bytes} B differs "
                  f"from XLA's collective")
    if min(spans) != n:
        _fail(f"arrays span {spans} devices, not {n}")


def check_tp_block(mesh, names, seed: int) -> None:
    """``transformer_block_tp`` on context collectives against the GSPMD
    ``transformer_block_ref``, one granite-width layer in float32."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.comms import api
    from repro.compat import shard_map
    from repro.configs import get_config
    from repro.models.model import (_layer_init, transformer_block_ref,
                                    transformer_block_tp, tp_block_specs)

    cfg = dataclasses.replace(get_config("granite-3-2b"), num_layers=1,
                              dtype="float32", remat=False)
    k_layer, k_x = jax.random.split(jax.random.key(seed))
    layer = _layer_init(k_layer, cfg, dtype=jnp.float32)
    B, S = 1, 128
    x = jax.random.normal(k_x, (B, S, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x_spec, l_spec = tp_block_specs(layer, names)
    layer = jax.device_put(layer, jax.tree.map(
        lambda s: NamedSharding(mesh, s), l_spec))
    x = jax.device_put(x, NamedSharding(mesh, x_spec))

    with jax.default_matmul_precision("highest"), \
            api.comm_context(mesh, names):
        got = jax.jit(shard_map(
            lambda lx, ll: transformer_block_tp(ll, cfg, lx, positions=pos),
            mesh=mesh, in_specs=(x_spec, l_spec), out_specs=x_spec))(x, layer)
        want = jax.jit(lambda lx, ll: transformer_block_ref(
            ll, cfg, lx, positions=pos))(x, layer)
    err = error_to_scale(got, want)
    spans = [len(a.sharding.device_set)
             for a in [x, got, want] + jax.tree.leaves(layer)]
    print(f"[smoke/tp-block] mesh={dict(mesh.shape)} granite-width layer "
          f"(B={B}, S={S}, float32): max|tp - gspmd| / max|gspmd| = "
          f"{err:.3e} (limit {TP_BLOCK_RTOL:.0e}); devices spanned "
          f"min={min(spans)}")
    if not err <= TP_BLOCK_RTOL:
        _fail(f"TP block differs from the GSPMD block: {err:.3e}")
    if min(spans) != mesh.devices.size:
        _fail(f"TP block arrays span {spans} devices")


def four_chips(seed: int, devices) -> None:
    from repro.compat import make_mesh

    for shape, names in (((4,), ("x",)), ((2, 2), ("a", "b"))):
        mesh = make_mesh(shape, names, devices=devices)
        for shard_bytes in (2**10, 2**20):
            check_collectives(mesh, names, shard_bytes)
        check_tp_block(mesh, names, seed)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip collectives and TP block")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    _require_tpu(4 if args.four_chips else 1)
    from repro.launch.device import (device_info, place_compile_cache,
                                     select_kernel_backend)

    cache = place_compile_cache()
    if args.four_chips:
        backend = "ref"  # the phase exercises collectives, not kernels
    else:
        backend = select_kernel_backend()
    info = device_info()
    if args.four_chips:
        import jax

        devices = jax.devices()[:4]
        info["count"] = len(devices)  # the chips the meshes use
    print(f"[smoke/device] platform={info['platform']} kind={info['kind']} "
          f"count={info['count']} kernels={backend} compile_cache={cache}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(args.seed, devices)
    else:
        one_chip(args.seed)
    print(f"[smoke/done] phases passed in {time.perf_counter() - t0:.1f}s "
          f"(host clock)")
    print(json.dumps({"ok": True, "device": info}))


if __name__ == "__main__":
    main()
