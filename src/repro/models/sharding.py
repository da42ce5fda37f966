"""Sharding rules: parameter/activation/cache PartitionSpecs.

Baseline layout (documented in DESIGN.md §6):
  * TP ('model'): attention QKV/O on heads-dim, FFN on the hidden dim,
    experts on the expert dim (EP), vocab/embed on the vocab dim.
  * DP ('data' [+ 'pod']): batch dim of activations; ZeRO-1 shards optimizer
    state over 'data' (see optim/).
Non-divisible dims (40 heads / 16-way model etc.) rely on GSPMD uneven
sharding; hillclimbed cells override these rules (launch/dryrun.py
--overrides).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..configs.base import ModelConfig, ShapeConfig

__all__ = [
    "dp_axes",
    "param_specs",
    "batch_specs",
    "cache_specs",
    "logits_spec",
    "named",
    "set_activation_policy",
    "constrain",
]

TP = "model"

#: module-level activation-sharding policy, installed by the launcher
#: (None => no constraints; models run un-annotated, e.g. CPU smoke tests)
_ACT_POLICY: Optional[Dict] = None


def set_activation_policy(policy: Optional[Dict]) -> None:
    """policy: {"dp": (..axis names..), "tp": "model", "sequence_parallel": bool}"""
    global _ACT_POLICY
    _ACT_POLICY = policy


def constrain(x, kind: str):
    """Annotate an activation tensor.

    kinds: 'hidden' (B,S,d) | 'logits' (B,S,V) | 'tokens_flat' (T,d) |
           'moe_buffer' (E,C,d) — expert-parallel over 'model'."""
    if _ACT_POLICY is None:
        return x
    dp = _ACT_POLICY["dp"]
    tp = _ACT_POLICY.get("tp", TP)
    if kind == "hidden":
        if _ACT_POLICY.get("sequence_parallel"):
            spec = P(dp, tp, None)
        else:
            spec = P(dp, None, None)
    elif kind == "logits":
        spec = P(dp, None, tp)
    elif kind == "tokens_flat":
        spec = P(dp, None)
    elif kind == "moe_buffer":  # (G, E, C, d): groups on dp, experts on tp
        spec = P(dp, tp, None, None)
    else:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def _leaf_spec(path: Tuple[str, ...], ndim: int) -> P:
    """Spec for one parameter leaf, path = tuple of dict keys (no layer dim)."""
    name = path[-1]
    parent = path[-2] if len(path) >= 2 else ""
    gparent = path[-3] if len(path) >= 3 else ""

    # embeddings / head
    if name == "embed":
        return P(TP, None)
    if parent == "lm_head":
        return P(None, TP)

    # attention projections
    if parent in ("wq", "wk", "wv") and gparent in ("attn", "tmix", "cmix"):
        return P(None, TP) if name == "w" else P(TP)
    if parent == "wo" and name in ("w", "b"):
        return P(TP, None) if name == "w" else P(None)
    if parent in ("wg", "wr") and name == "w":
        return P(None, TP)
    if parent in ("wg", "wr") and name == "b":
        return P(TP)

    # dense FFN (also shared/dense branches of MoE)
    if parent in ("gate", "up") and name == "w":
        return P(None, TP)
    if parent == "down" and name == "w":
        return P(TP, None)
    # moe expert tensors are stacked (E, d, f)/(E, f, d): EP over experts
    if parent == "experts":
        return P(TP, None, None)
    if parent == "router":
        return P(None, None)

    # rwkv specifics
    if name == "u":
        return P(TP, None)
    if name in ("mu", "lora_a", "lora_b", "w0", "w_lora_a", "w_lora_b",
                "mu_k", "mu_r"):
        return P(*([None] * ndim))

    # mamba2
    if parent == "in_proj" and name == "w":
        return P(None, TP)
    if parent == "out_proj" and name == "w":
        return P(TP, None)
    if name == "conv_w":
        return P(None, TP)
    if name == "conv_b":
        return P(TP)
    if name in ("a_log", "d_skip", "dt_bias", "norm_scale"):
        return P(*([None] * ndim))

    # norms and anything residual: replicate
    return P(*([None] * ndim))


def param_specs(cfg: ModelConfig, params) -> Dict:
    """Pytree of PartitionSpec matching ``params``."""

    def spec(path, leaf):
        keys = tuple(
            p.key if hasattr(p, "key") else str(p) for p in path
        )
        ndim = leaf.ndim
        if keys and keys[0] == "layers":
            # scanned leaves carry a leading layer dim
            inner = _leaf_spec(("layers",) + keys[1:], ndim - 1)
            return P(None, *inner)
        if keys and keys[0] == "embed":
            return P(TP, None)
        return _leaf_spec(keys, ndim)

    return jax.tree_util.tree_map_with_path(spec, params)


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh) -> Dict:
    dp = dp_axes(mesh)
    specs: Dict[str, P] = {}
    if cfg.frontend == "audio":
        specs["embeds"] = P(dp, None, None)
    else:
        specs["tokens"] = P(dp, None)
    if cfg.frontend == "vision" and shape.kind != "decode":
        specs["image_embeds"] = P(dp, None, None)
    if shape.kind == "train":
        specs["labels"] = P(dp, None)
    if shape.kind == "decode":
        specs["cache_pos"] = P()
    return specs


def cache_specs(cfg: ModelConfig, mesh: Mesh) -> Dict:
    dp = dp_axes(mesh)
    tp_size = mesh.shape.get(TP, 1)
    # KV heads shard over 'model' when divisible; otherwise shard the
    # sequence dim (flash-decoding-style sharded-KV attention — GSPMD
    # inserts the softmax combine collectives).  cfg.kv_shard overrides.
    if cfg.kv_shard == "heads" or (
        cfg.kv_shard == "auto" and cfg.num_kv_heads % tp_size == 0
    ):
        kv = P(None, dp, TP, None, None)
    else:
        kv = P(None, dp, None, TP, None)
    mamba = {"conv": P(None, dp, None, TP), "ssm": P(None, dp, TP, None, None)}
    if cfg.layer_types:
        specs = {}
        if "mamba" in cfg.layer_types:
            specs["mamba"] = mamba
        if "attention" in cfg.layer_types:
            specs["k"] = specs["v"] = kv
        return specs
    if cfg.family == "ssm":
        return {
            "rwkv": {
                "tmix_x": P(None, dp, None),
                "cmix_x": P(None, dp, None),
                "wkv": P(None, dp, TP, None, None),
            }
        }
    if cfg.family == "hybrid":
        return {
            "mamba": mamba,
            "shared_k": kv,
            "shared_v": kv,
        }
    return {"k": kv, "v": kv}


def logits_spec(mesh: Mesh) -> P:
    return P(dp_axes(mesh), None, TP)


def _axes_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    names = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for n in names:
        size *= mesh.shape[n]
    return size


def sanitize_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop sharded dims whose size is not divisible by the mesh axes
    (pjit requires exact divisibility for explicit in/out shardings).
    GSPMD-internal ops may still shard unevenly; top-level args cannot."""
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for entry, dim in zip(parts, shape):
        size = _axes_size(mesh, entry)
        out.append(entry if (size > 1 and dim % size == 0) or size == 1 else None)
    return P(*out)


def sanitize_tree(specs, shapes, mesh: Mesh):
    """Sanitize a pytree of PartitionSpec against ShapeDtypeStructs."""
    return jax.tree.map(
        lambda s, sds: sanitize_spec(s, sds.shape, mesh),
        specs, shapes, is_leaf=lambda x: isinstance(x, P),
    )


def fsdp_tree(specs, shapes, mesh: Mesh):
    """ZeRO-3/FSDP: additionally shard every param over 'data' on its first
    unsharded divisible dim.  Per-layer all-gathers are emitted by GSPMD
    inside the layer scan — the OpTree-staged gather pattern on the
    multi-pod mesh (pod axis carries only the 1/data shard)."""
    data = mesh.shape.get("data", 1)

    def f(spec, sds):
        parts = list(spec) + [None] * (len(sds.shape) - len(spec))
        used = {a for p in parts if p is not None
                for a in (p if isinstance(p, tuple) else (p,))}
        if "data" in used:
            return P(*parts)
        for i, (p, dim) in enumerate(zip(parts, sds.shape)):
            if p is None and dim % data == 0 and dim >= data:
                parts[i] = "data"
                break
        return P(*parts)

    return jax.tree.map(f, specs, shapes, is_leaf=lambda x: isinstance(x, P))


def named(mesh: Mesh, tree_specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))
