import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks the device count on first
# init).  Everything below is ordinary code.

DOC = """Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real step function (train_step = fwd+bwd+AdamW;
prefill = forward installing KV; decode = one-token serve step), lowers it
with ShapeDtypeStruct stand-ins (zero allocation), compiles it for the
production mesh, and records:

  * memory_analysis()      — proves the cell fits per-device HBM,
  * cost_analysis()        — HLO FLOPs / bytes for the roofline,
  * collective traffic     — parsed from the optimized HLO text,
  * wall compile time.

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out runs/dryrun]
  python -m repro.launch.dryrun --all --both-meshes --out runs/dryrun
"""

import argparse
import dataclasses
import functools
import json
import re
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (
    SHAPES,
    get_config,
    input_specs,
    list_archs,
    shape_supported,
)
from repro.launch.mesh import make_production_mesh
from repro.models import (
    decode_step,
    forward,
    init_decode_state,
    init_params,
    loss_fn,
)
from repro.models import sharding as shd
from repro.optim import OptimizerConfig, adamw_init, adamw_update, opt_state_specs

__all__ = ["dryrun_cell", "main"]


# --------------------------------------------------------------------------
# HLO collective parsing
# --------------------------------------------------------------------------
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2, "u16": 2, "f8e4m3fn": 1,
}
_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_DEF_RE = re.compile(
    r"%([\w.-]+)\s*=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?)\s+([a-z0-9-]+)\("
)
_OPERAND_RE = re.compile(r"%([\w.-]+)")


def _parse_result_bytes(type_str: str) -> int:
    total = 0
    for sm in _SHAPE_RE.finditer(type_str):
        total += _shape_bytes(sm.group(1), sm.group(2))
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, Any]:
    """Sum operand bytes of every collective op in the optimized HLO.

    Optimized HLO references operands by name only, so pass 1 builds a
    symbol table name -> result bytes, and pass 2 resolves each collective's
    operand list against it.  (Result bytes are recorded too: for all-gather
    the *result* is the transferred payload upper bound, for reduce-scatter
    the *operand* is.)
    """
    sizes: Dict[str, int] = {}
    lines = hlo_text.splitlines()
    for line in lines:
        m = _DEF_RE.search(line)
        if m:
            sizes[m.group(1)] = _parse_result_bytes(m.group(2))

    per_kind_operand: Dict[str, int] = {}
    per_kind_result: Dict[str, int] = {}
    counts: Dict[str, int] = {}
    for line in lines:
        m = _DEF_RE.search(line)
        if not m:
            continue
        name, type_str, op = m.group(1), m.group(2), m.group(3)
        kind = next((c for c in _COLLECTIVES if op.startswith(c)), None)
        if kind is None:
            continue
        # operand list: inside the call parens, before attributes
        call = line[m.end() - 1 :]
        depth = 0
        end = len(call)
        for i, ch in enumerate(call):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        operand_bytes = sum(
            sizes.get(om.group(1), 0) for om in _OPERAND_RE.finditer(call[:end])
        )
        per_kind_operand[kind] = per_kind_operand.get(kind, 0) + operand_bytes
        per_kind_result[kind] = per_kind_result.get(kind, 0) + _parse_result_bytes(type_str)
        counts[kind] = counts.get(kind, 0) + 1
    return {
        "bytes_by_kind": per_kind_operand,
        "result_bytes_by_kind": per_kind_result,
        "counts": counts,
        "total_bytes": sum(per_kind_operand.values()),
        "total_result_bytes": sum(per_kind_result.values()),
    }


# --------------------------------------------------------------------------
# cell construction
# --------------------------------------------------------------------------
def _tree_specs_to_shardings(mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def build_cell(arch: str, shape_name: str, mesh, *, overrides: Optional[Dict] = None):
    """Returns (fn, arg_sds, in_shardings, out_shardings, meta)."""
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_supported(cfg, shape)
    if not ok:
        raise ValueError(f"{arch} x {shape_name} skipped: {why}")

    # sequence-parallel activations for training: the per-layer remat carry
    # (B, S, d) is sharded over 'model' between blocks — the induced
    # gather/scatter pattern is exactly the staged all-gather the paper
    # optimizes (see DESIGN.md §3); decode/prefill keep replicated hiddens.
    shd.set_activation_policy(
        {"dp": shd.dp_axes(mesh), "tp": "model",
         "sequence_parallel": cfg.sequence_parallel and shape.kind == "train"}
    )

    params_sds = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.key(0))
    pspecs = shd.sanitize_tree(shd.param_specs(cfg, params_sds), params_sds, mesh)
    if cfg.fsdp:
        pspecs = shd.fsdp_tree(pspecs, params_sds, mesh)
    batch_sds = input_specs(cfg, shape)
    bspecs = shd.sanitize_tree(shd.batch_specs(cfg, shape, mesh), batch_sds, mesh)
    dp = shd.dp_axes(mesh)

    if shape.kind == "train":
        opt_cfg = OptimizerConfig(
            state_dtype=cfg.opt_state_dtype, use_master=cfg.opt_use_master
        )
        opt_sds = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_sds)
        ospecs = opt_state_specs(pspecs, params_sds, mesh,
                                 with_master=cfg.opt_use_master)
        ospecs = shd.sanitize_tree(ospecs, opt_sds, mesh)

        def train_step(params, opt_state, batch):
            A = cfg.grad_accum
            if A <= 1:
                (_, metrics), grads = jax.value_and_grad(
                    lambda p: loss_fn(cfg, p, batch), has_aux=True
                )(params)
            else:
                # microbatched gradient accumulation: peak activation memory
                # scales with B/A, grads/optimizer traffic unchanged
                micro = jax.tree.map(
                    lambda a: a.reshape((A, a.shape[0] // A) + a.shape[1:]), batch
                )

                def acc_body(carry, mb):
                    gacc, lacc = carry
                    (_, m), g = jax.value_and_grad(
                        lambda p: loss_fn(cfg, p, mb), has_aux=True
                    )(params)
                    return (jax.tree.map(jnp.add, gacc, g),
                            lacc + m["loss"]), 0

                zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, p.dtype), params)
                (grads, loss_sum), _ = jax.lax.scan(
                    acc_body, (zeros, jnp.zeros((), jnp.float32)), micro
                )
                grads = jax.tree.map(lambda g: g / A, grads)
                metrics = {"loss": loss_sum / A}
            new_params, new_opt = adamw_update(grads, opt_state, params, opt_cfg)
            return new_params, new_opt, metrics["loss"]

        fn = train_step
        args = (params_sds, opt_sds, batch_sds)
        in_specs = (pspecs, ospecs, bspecs)
        out_specs = (pspecs, ospecs, P())

    elif shape.kind == "prefill":
        cache_sds = jax.eval_shape(
            lambda: init_decode_state(cfg, shape.global_batch, shape.seq_len)
        )
        cspecs = shd.sanitize_tree(shd.cache_specs(cfg, mesh), cache_sds, mesh)

        def prefill_step(params, batch, cache):
            # production prefill: install KV/state, emit last-token logits
            logits, new_cache, _ = forward(
                cfg, params, batch, cache=cache,
                cache_pos=jnp.zeros((), jnp.int32), head_mode="last",
            )
            return logits, new_cache

        fn = prefill_step
        args = (params_sds, batch_sds, cache_sds)
        in_specs = (pspecs, bspecs, cspecs)
        out_specs = (
            shd.sanitize_spec(
                P(dp, "model"), (shape.global_batch, cfg.vocab_size), mesh
            ),
            cspecs,
        )

    else:  # decode
        cache_sds = jax.eval_shape(
            lambda: init_decode_state(cfg, shape.global_batch, shape.seq_len)
        )
        cspecs = shd.sanitize_tree(shd.cache_specs(cfg, mesh), cache_sds, mesh)
        tokens_sds = batch_sds.pop("tokens")
        pos_sds = batch_sds.pop("cache_pos")

        def serve_step(params, state, tokens, pos):
            return decode_step(cfg, params, state, tokens, pos)

        fn = serve_step
        args = (params_sds, cache_sds, tokens_sds, pos_sds)
        in_specs = (
            pspecs,
            cspecs,
            shd.sanitize_spec(P(dp, None), tokens_sds.shape, mesh),
            P(),
        )
        out_specs = (
            shd.sanitize_spec(
                P(dp, "model"), (shape.global_batch, cfg.vocab_size), mesh
            ),
            cspecs,
        )

    in_shard = _tree_specs_to_shardings(mesh, in_specs)
    out_shard = _tree_specs_to_shardings(mesh, out_specs)
    meta = {"arch": arch, "shape": shape_name, "kind": shape.kind,
            "mesh": dict(mesh.shape)}
    return fn, args, in_shard, out_shard, meta


def _compile_cell(arch, shape_name, mesh, overrides):
    fn, args, in_shard, out_shard, meta = build_cell(
        arch, shape_name, mesh, overrides=overrides
    )
    with mesh:
        compiled = (
            jax.jit(fn, in_shardings=in_shard, out_shardings=out_shard)
            .lower(*args)
            .compile()
        )
    return compiled


def calibrated_costs(
    arch: str, shape_name: str, mesh, overrides: Optional[Dict] = None
) -> Dict[str, Any]:
    """Correct for HloCostAnalysis counting while-loop (scan) bodies once:
    lower the same cell UNROLLED at depth u and 2u, then extrapolate
    total = f(u) + (L/u - 1) * (f(2u) - f(u)).  u = hybrid_attn_every for
    the hybrid arch (its repeating unit spans `every` layers), the period
    of a ``layer_types`` arch, else 1."""
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    if cfg.layer_types:
        u = len(cfg.layer_period)
    else:
        u = cfg.hybrid_attn_every if cfg.family == "hybrid" else 1
    probes = {}
    for n in (u, 2 * u):
        ov = dict(overrides or {})
        ov.update(num_layers=n, scan_layers=False)
        if cfg.layer_types:
            ov["layer_types"] = cfg.layer_period * (n // u)
        compiled = _compile_cell(arch, shape_name, mesh, ov)
        cost = compiled.cost_analysis()
        probes[n] = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": collective_bytes_from_hlo(compiled.as_text()),
        }
    scale = cfg.num_layers // u - 1
    a, b = probes[u], probes[2 * u]

    def comb(x, y):
        return x + scale * (y - x)

    kinds = set(a["coll"]["bytes_by_kind"]) | set(b["coll"]["bytes_by_kind"])
    coll_kinds = {
        k: comb(a["coll"]["bytes_by_kind"].get(k, 0),
                b["coll"]["bytes_by_kind"].get(k, 0))
        for k in kinds
    }
    return {
        "flops": comb(a["flops"], b["flops"]),
        "bytes_accessed": comb(a["bytes"], b["bytes"]),
        "collective_bytes_by_kind": coll_kinds,
        "collective_bytes": sum(coll_kinds.values()),
        "probe_depths": [u, 2 * u],
    }


def dryrun_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    overrides: Optional[Dict] = None,
    hlo_out: Optional[Path] = None,
    calibrate: bool = True,
) -> Dict[str, Any]:
    mesh = make_production_mesh(multi_pod=multi_pod)
    fn, args, in_shard, out_shard, meta = build_cell(
        arch, shape_name, mesh, overrides=overrides
    )
    t0 = time.perf_counter()
    with mesh:
        lowered = jax.jit(fn, in_shardings=in_shard, out_shardings=out_shard).lower(*args)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
    t_compile = time.perf_counter() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes_from_hlo(hlo)
    if hlo_out is not None:
        hlo_out.parent.mkdir(parents=True, exist_ok=True)
        hlo_out.write_text(hlo)

    result = {
        **meta,
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "collectives": coll,
        "memory": {
            k: getattr(mem, k, None)
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
        } if mem is not None else None,
    }
    if calibrate:
        result["calibrated"] = calibrated_costs(
            arch, shape_name, mesh, overrides=overrides
        )
    print(f"[dryrun] {arch} x {shape_name} mesh={meta['mesh']} "
          f"compile={t_compile:.1f}s flops={result['flops']} "
          f"coll={coll['total_bytes']:.3e}B"
          + (f" cal_flops={result['calibrated']['flops']:.3e}" if calibrate else ""))
    print(f"[dryrun]   memory_analysis: {result['memory']}")
    return result


# --------------------------------------------------------------------------
def iter_cells():
    for arch in list_archs():
        cfg = get_config(arch)
        for shape in SHAPES.values():
            ok, why = shape_supported(cfg, shape)
            yield arch, shape.name, ok, why


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="runs/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    cells = (
        [(a, s) for a, s, ok, _ in iter_cells() if ok]
        if args.all
        else [(args.arch, args.shape)]
    )

    failures = []
    for multi_pod in meshes:
        tag = "multipod" if multi_pod else "singlepod"
        for arch, shape in cells:
            cell_file = out / f"{arch}__{shape}__{tag}.json"
            if cell_file.exists():
                print(f"[dryrun] skip existing {cell_file.name}")
                continue
            try:
                hlo_path = (
                    out / "hlo" / f"{arch}__{shape}__{tag}.txt"
                    if args.save_hlo else None
                )
                res = dryrun_cell(arch, shape, multi_pod=multi_pod,
                                  hlo_out=hlo_path)
            except Exception as e:  # noqa: BLE001 — record and continue
                res = {"arch": arch, "shape": shape, "ok": False,
                       "mesh": tag, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-2000:]}
                failures.append((arch, shape, tag))
                print(f"[dryrun] FAIL {arch} x {shape} ({tag}): {e}")
            cell_file.write_text(json.dumps(res, indent=2, default=str))

    # skip report
    skip_file = out / "skips.json"
    skips = [
        {"arch": a, "shape": s, "reason": why}
        for a, s, ok, why in iter_cells() if not ok
    ]
    skip_file.write_text(json.dumps(skips, indent=2))
    print(f"[dryrun] done; {len(failures)} failures; skips -> {skip_file}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
