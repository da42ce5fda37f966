"""Mixture-of-Experts block: top-k routing with sort-based capacity dispatch.

Dispatch is scatter/gather based (Megablocks-style), not the GShard one-hot
einsum: the (tokens, experts, capacity) one-hot tensor is O(T*E*C) and
explodes at arctic scale (1M tokens x 128 experts); the sort path stays
O(T*K*d + E*C*d) and shards cleanly with experts on the 'model' axis
(expert parallelism) and capacity on the 'data' axis.

Supports:
  * top-1 + always-on shared expert (llama4-scout),
  * top-2 + parallel dense residual FFN (arctic),
  * load-balance + router-z auxiliary losses.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..configs.base import ModelConfig
from .layers import dense, dense_init
from .mlp import ffn_apply, ffn_init
from .sharding import constrain

__all__ = ["moe_init", "moe_block"]


def _ep_active(axis_name: str) -> bool:
    """True when ``axis_name`` is bound in the ambient axis env — i.e. we
    are tracing inside a shard_map body that carries the expert axis."""
    try:
        lax.axis_size(axis_name)
        return True
    except Exception:
        return False


def moe_init(key, cfg: ModelConfig, *, dtype) -> Dict:
    e = cfg.moe
    assert e is not None
    ks = jax.random.split(key, 4)
    d = cfg.d_model

    def expert_init(k):
        kk = jax.random.split(k, 3)
        scale = 0.02 / (2 * cfg.num_layers) ** 0.5
        return {
            "gate": (jax.random.normal(kk[0], (d, e.d_ff_expert)) * 0.02).astype(dtype),
            "up": (jax.random.normal(kk[1], (d, e.d_ff_expert)) * 0.02).astype(dtype),
            "down": (jax.random.normal(kk[2], (e.d_ff_expert, d)) * scale).astype(dtype),
        }

    p = {
        "router": dense_init(ks[0], d, e.num_experts, dtype=jnp.float32, scale=0.01),
        "experts": jax.vmap(expert_init)(jax.random.split(ks[1], e.num_experts)),
    }
    if e.shared_expert:
        p["shared"] = ffn_init(ks[2], d, e.d_ff_expert, cfg.num_layers, dtype=dtype)
    if e.dense_residual:
        p["dense"] = ffn_init(ks[3], d, cfg.d_ff, cfg.num_layers, dtype=dtype)
    return p


def _expert_ffn(experts: Dict, xe: jax.Array) -> jax.Array:
    """xe: (E, C, d) -> (E, C, d); batched over experts (EP-shardable)."""
    g = jnp.einsum("ecd,edf->ecf", xe, experts["gate"])
    u = jnp.einsum("ecd,edf->ecf", xe, experts["up"])
    h = (jax.nn.silu(g.astype(jnp.float32)) * u.astype(jnp.float32)).astype(xe.dtype)
    return jnp.einsum("ecf,efd->ecd", h, experts["down"])


def _ep_expert_ffn(experts: Dict, buf: jax.Array, axis_name: str) -> jax.Array:
    """Expert-parallel (G, E, C, d) -> (G, E, C, d): each device owns
    E/m contiguous experts along mesh axis ``axis_name``.

    The dispatch buffer's expert dim is owner-major (experts contiguous per
    owner device), so one context-planned ``api.all_to_all`` ships every
    device's per-expert slices to the expert owners, the local expert shard
    runs on the concatenated arrivals, and the inverse all-to-all returns
    the results to the token owners — the only cross-device movement, and
    it flows through the same CollectivePlan IR the pricer and the optical
    simulator consume.

    ``experts`` may hold the full (E, ...) stacked weights (replicated
    params, e.g. the explicit-ZeRO1 trainer: this device's shard is sliced
    out locally, so gradients land in the right slice) or an already-local
    (E/m, ...) shard."""
    from ..comms import api  # lazy: models must stay importable without comms

    m = lax.axis_size(axis_name)
    G, E, C, d = buf.shape
    if E % m:
        raise ValueError(
            f"num_experts {E} not divisible by expert axis "
            f"{axis_name!r} size {m}")
    e_loc = E // m
    w_gate, w_up, w_down = experts["gate"], experts["up"], experts["down"]
    if w_gate.shape[0] == E and m > 1:
        idx = lax.axis_index(axis_name)

        def sl(w):
            return lax.dynamic_slice_in_dim(w, idx * e_loc, e_loc, axis=0)

        w_gate, w_up, w_down = sl(w_gate), sl(w_up), sl(w_down)
    elif w_gate.shape[0] != e_loc:
        raise ValueError(
            f"expert weights have leading dim {w_gate.shape[0]}; expected "
            f"{E} (replicated) or {e_loc} (local shard) for "
            f"{m}-way expert parallelism")

    # (G,E,C,d) -> (E,G,C,d) -> (E·G·C, d): destination block v = the
    # slices for experts [v·e_loc, (v+1)·e_loc) — owner-major by experts
    z = jnp.swapaxes(buf, 0, 1).reshape(E * G * C, d)
    z = api.all_to_all(z, axes=(axis_name,))
    # received block u = device u's slices for MY experts
    z = jnp.swapaxes(z.reshape(m, e_loc, G, C, d), 0, 1)
    y = _expert_ffn(
        {"gate": w_gate, "up": w_up, "down": w_down},
        z.reshape(e_loc, m * G * C, d),
    )
    # inverse exchange: results back to the token owners, expert-major
    y = jnp.swapaxes(y.reshape(e_loc, m, G, C, d), 0, 1).reshape(E * G * C, d)
    y = api.all_to_all(y, axes=(axis_name,))
    return jnp.swapaxes(y.reshape(E, G, C, d), 0, 1)


def _num_groups(T: int, want: int = 32) -> int:
    g = min(want, T)
    while T % g:
        g -= 1
    return g


def moe_block(
    p: Dict, cfg: ModelConfig, x: jax.Array
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, S, d) -> (out, aux_losses).

    Group-local dispatch: tokens are split into G data-parallel groups; the
    argsort / rank / scatter bookkeeping never crosses a group boundary, so
    under pjit those ops stay shard-local and the only cross-device movement
    is the (G, E, C, d) <-> expert-weights contraction — the EP all-to-all.
    (A global argsort permutes tokens across the whole data axis every layer;
    that cost arctic-480b 16 TB/step of all-reduce — EXPERIMENTS.md §Perf.)

    With ``cfg.moe.expert_axis`` set AND that axis bound in the ambient axis
    env (tracing inside shard_map), the EP all-to-all is EXPLICIT: experts
    shard over the axis and ``_ep_expert_ffn`` routes dispatch/combine
    through ``repro.comms.api.all_to_all`` — context-planned, plan-cached,
    and numerically identical to running this block per device shard with
    all experts local.
    """
    e = cfg.moe
    B, S, d = x.shape
    T = B * S
    K, E = e.top_k, e.num_experts
    G = _num_groups(T)
    Tg = T // G
    C = max(1, math.ceil(K * Tg / E * e.capacity_factor))

    xt = x.reshape(T, d)
    xg = x.reshape(G, Tg, d)
    router_logits = dense(p["router"], xg.astype(jnp.float32))  # (G, Tg, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)  # (G, Tg, K)
    if K > 1:
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- aux losses (Switch-style, over all tokens) ----
    me = jnp.mean(probs, axis=(0, 1))  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_ids, E, dtype=jnp.float32), axis=2),
        axis=(0, 1),
    )
    aux = {
        "load_balance": E * jnp.sum(me * ce),
        "router_z": jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2),
    }

    # ---- group-local sort-based dispatch ----
    flat_ids = expert_ids.reshape(G, Tg * K)
    order = jnp.argsort(flat_ids, axis=-1)  # (G, TgK), stable per group
    sorted_ids = jnp.take_along_axis(flat_ids, order, axis=-1)
    run_start = jax.vmap(lambda s: jnp.searchsorted(s, s, side="left"))(sorted_ids)
    pos_in_expert = jnp.arange(Tg * K)[None, :] - run_start
    keep = pos_in_expert < C
    pos_c = jnp.where(keep, pos_in_expert, C)  # C is OOB -> mode='drop'

    src_token = order // K  # (G, TgK) indices into the group's tokens

    def scatter_group(xg_g, ids_g, pos_g, src_g):
        gathered = xg_g[src_g]  # (TgK, d)
        return jnp.zeros((E, C, d), x.dtype).at[ids_g, pos_g].set(
            gathered, mode="drop"
        )

    buf = jax.vmap(scatter_group)(xg, sorted_ids, pos_c, src_token)  # (G,E,C,d)
    buf = constrain(buf, "moe_buffer")

    ep = e.expert_axis is not None and _ep_active(e.expert_axis)
    if ep:
        # experts live on the mesh: dispatch/combine cross it through the
        # context-planned all-to-all (comms.api); aux means become global
        # below.  Routing/capacity above is group-local per device, exactly
        # the math of the non-EP block on this device's tokens.
        ye = _ep_expert_ffn(p["experts"], buf, e.expert_axis)  # (G,E,C,d)
        aux = {k: lax.pmean(v, e.expert_axis) for k, v in aux.items()}
    else:
        g_ = jnp.einsum("gecd,edf->gecf", buf, p["experts"]["gate"])
        u_ = jnp.einsum("gecd,edf->gecf", buf, p["experts"]["up"])
        h_ = (jax.nn.silu(g_.astype(jnp.float32)) * u_.astype(jnp.float32)).astype(x.dtype)
        ye = jnp.einsum("gecf,efd->gecd", h_, p["experts"]["down"])  # (G,E,C,d)
    ye = constrain(ye, "moe_buffer")

    pos_clip = jnp.minimum(pos_c, C - 1)

    def gather_group(ye_g, ids_g, pos_g, keep_g, src_g, gates_g):
        rows = ye_g[ids_g, pos_g]  # (TgK, d)
        rows = jnp.where(keep_g[:, None], rows, 0.0)
        contrib = rows * gates_g[:, None].astype(rows.dtype)
        return jnp.zeros((Tg, d), x.dtype).at[src_g].add(contrib)

    gates_sorted = jnp.take_along_axis(
        gate_vals.reshape(G, Tg * K), order, axis=-1
    )
    out = jax.vmap(gather_group)(
        ye, sorted_ids, pos_clip, keep, src_token, gates_sorted
    )  # (G, Tg, d)
    out = out.reshape(T, d)

    if e.shared_expert:
        out = out + ffn_apply(p["shared"], xt)
    if e.dense_residual:
        out = out + ffn_apply(p["dense"], xt)
    return out.reshape(B, S, d), aux
