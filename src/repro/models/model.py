"""Config-driven model assembly: init / forward / loss / decode.

One code path covers all ten assigned architectures:

  dense | moe | vlm | audio : [ln -> attention -> ln -> FFN/MoE] x L
  ssm (rwkv6)               : [ln -> time-mix -> ln -> channel-mix] x L
  hybrid (zamba2)           : [ln -> mamba2] x L (+ one *shared* attn+FFN
                              block invoked every cfg.hybrid_attn_every
                              layers, weights reused, per-invocation KV)

Layers are stacked and run under ``lax.scan`` (keeps the HLO O(1) in depth —
essential for 64-layer 32B configs on the dry-run) with optional per-layer
remat.  MoE aux losses are accumulated through the scan carry.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .attention import (
    attention,
    attention_heads,
    attention_tp_out,
    attention_tp_out_sp,
    attn_init,
)
from .layers import rmsnorm, rmsnorm_init
from .mamba2 import mamba2_block, mamba2_init, mamba2_state_init
from .mlp import ffn_apply, ffn_apply_tp, ffn_apply_tp_sp, mlp, mlp_init
from .moe import moe_block, moe_init
from .rwkv6 import (
    rwkv6_channel_mix,
    rwkv6_init,
    rwkv6_state_init,
    rwkv6_time_mix,
)
from .sharding import constrain

__all__ = ["init_params", "forward", "loss_fn", "init_decode_state",
           "decode_step", "transformer_block_tp", "transformer_block_ref",
           "tp_block_specs"]

ZERO_AUX = lambda: {"load_balance": jnp.zeros((), jnp.float32),
                    "router_z": jnp.zeros((), jnp.float32)}


def _maybe_checkpoint(cfg: ModelConfig, body):
    if not cfg.remat:
        return body
    if cfg.remat_policy == "dots":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return jax.checkpoint(body)


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------
def _layer_init(key, cfg: ModelConfig, *, dtype) -> Dict:
    ks = jax.random.split(key, 4)
    if cfg.family == "ssm":  # rwkv6
        p = rwkv6_init(ks[0], cfg, dtype=dtype)
        return {
            "ln1": rmsnorm_init(cfg.d_model, dtype=dtype),
            "tmix": p["tmix"],
            "ln2": rmsnorm_init(cfg.d_model, dtype=dtype),
            "cmix": p["cmix"],
        }
    if cfg.family == "hybrid":  # zamba2 backbone layer
        return {
            "ln1": rmsnorm_init(cfg.d_model, dtype=dtype),
            "mamba": mamba2_init(ks[0], cfg, dtype=dtype),
        }
    layer = {
        "ln1": rmsnorm_init(cfg.d_model, dtype=dtype),
        "attn": attn_init(ks[0], cfg, dtype=dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype=dtype),
    }
    if cfg.moe is not None:
        layer["moe"] = moe_init(ks[1], cfg, dtype=dtype)
    else:
        layer["ffn"] = mlp_init(ks[1], cfg, dtype=dtype)
    return layer


def init_params(key, cfg: ModelConfig) -> Dict:
    dtype = jnp.dtype(cfg.dtype)
    k_embed, k_layers, k_head, k_shared = jax.random.split(key, 4)
    params: Dict[str, Any] = {}
    if cfg.frontend != "audio":
        # vocab rows are padded to cfg.padded_vocab so the vocab dim shards
        # evenly; the pad region is zero and masked out of loss/decode
        params["embed"] = (jax.random.normal(k_embed, (cfg.padded_vocab, cfg.d_model))
                           * 0.02).astype(dtype)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    params["layers"] = jax.vmap(
        functools.partial(_layer_init, cfg=cfg, dtype=dtype)
    )(layer_keys)
    if cfg.hybrid_attn_every:
        params["shared_block"] = {
            "ln1": rmsnorm_init(cfg.d_model, dtype=dtype),
            "attn": attn_init(k_shared, cfg, dtype=dtype),
            "ln2": rmsnorm_init(cfg.d_model, dtype=dtype),
            "ffn": mlp_init(jax.random.fold_in(k_shared, 1), cfg, dtype=dtype),
        }
    params["final_norm"] = rmsnorm_init(cfg.d_model, dtype=dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "w": (jax.random.normal(k_head, (cfg.d_model, cfg.padded_vocab)) * 0.02
                  ).astype(dtype)
        }
    return params


# --------------------------------------------------------------------------
# decode state
# --------------------------------------------------------------------------
def init_decode_state(cfg: ModelConfig, batch: int, max_seq: int) -> Dict:
    dtype = jnp.dtype(cfg.dtype)
    L = cfg.num_layers

    def stack(tree):
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (L,) + a.shape).copy(), tree)

    if cfg.family == "ssm":
        return {"rwkv": stack(rwkv6_state_init(cfg, batch, dtype=dtype))}
    if cfg.family == "hybrid":
        n_shared = L // cfg.hybrid_attn_every
        kv_shape = (n_shared, batch, cfg.num_kv_heads, max_seq, cfg.head_dim)
        return {
            "mamba": stack(mamba2_state_init(cfg, batch, dtype=dtype)),
            "shared_k": jnp.zeros(kv_shape, dtype),
            "shared_v": jnp.zeros(kv_shape, dtype),
        }
    kv_shape = (L, batch, cfg.num_kv_heads, max_seq, cfg.head_dim)
    return {"k": jnp.zeros(kv_shape, dtype), "v": jnp.zeros(kv_shape, dtype)}


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _embed_inputs(cfg: ModelConfig, params: Dict, batch: Dict) -> jax.Array:
    if cfg.frontend == "audio":
        return batch["embeds"].astype(jnp.dtype(cfg.dtype))
    x = jnp.take(params["embed"], batch["tokens"], axis=0)
    if cfg.frontend == "vision" and "image_embeds" in batch:
        img = batch["image_embeds"].astype(x.dtype)
        x = jax.lax.dynamic_update_slice(x, img, (0, 0, 0))
    return x


def _keep_lanes(lanes, old, new):
    """``new`` on the batch lanes set in ``lanes`` ((B,) bool, or None for
    all), ``old`` elsewhere; batch is every leaf's leading dim."""
    if lanes is None:
        return new
    return jax.tree.map(
        lambda o, n: jnp.where(lanes.reshape((-1,) + (1,) * (n.ndim - 1)),
                               n, o), old, new)


def _attn_layer_body(cfg, layer, x, positions, kv, cache_pos,
                     cache_lanes=None):
    h, new_kv = attention(
        layer["attn"], cfg, rmsnorm(layer["ln1"], x, cfg.norm_eps),
        positions=positions, kv_cache=kv, cache_pos=cache_pos,
        cache_lanes=cache_lanes,
    )
    x = x + h
    aux = ZERO_AUX()
    if cfg.moe is not None:
        h, aux = moe_block(layer["moe"], cfg, rmsnorm(layer["ln2"], x, cfg.norm_eps))
    else:
        h = mlp(layer["ffn"], cfg, rmsnorm(layer["ln2"], x, cfg.norm_eps))
    x = constrain(x + h, "hidden")
    return x, new_kv, aux


def _rwkv_layer_body(cfg, layer, x, state):
    st = state or {}
    h, last_t, wkv = rwkv6_time_mix(
        layer["tmix"], cfg, rmsnorm(layer["ln1"], x, cfg.norm_eps),
        last_x=st.get("tmix_x"), wkv_state=st.get("wkv"),
    )
    x = x + h
    h, last_c = rwkv6_channel_mix(
        layer["cmix"], cfg, rmsnorm(layer["ln2"], x, cfg.norm_eps),
        last_x=st.get("cmix_x"),
    )
    x = constrain(x + h, "hidden")
    new_state = {"tmix_x": last_t, "cmix_x": last_c, "wkv": wkv}
    return x, new_state


def _scan_or_loop(body, carry, xs, length: int, use_scan: bool):
    """lax.scan or an unrolled python loop (scan_layers=False: used by the
    roofline flops calibration, where while-loop trip counts hide cost)."""
    if use_scan:
        return jax.lax.scan(body, carry, xs)
    ys_list = []
    for i in range(length):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys_list.append(y)
    ys = jax.tree.map(lambda *a: jnp.stack(a), *ys_list)
    return carry, ys


def apply_head(cfg: ModelConfig, params: Dict, x: jax.Array) -> jax.Array:
    """Final-norm'd hidden -> (padded-)vocab logits in f32 (accumulated and
    returned in f32: bf16 logits would round away the gaps greedy decoding
    and the loss depend on)."""
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x, params["embed"],
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bsd,dv->bsv", x, params["lm_head"]["w"],
                      preferred_element_type=jnp.float32)


def forward(
    cfg: ModelConfig,
    params: Dict,
    batch: Dict,
    *,
    cache: Optional[Dict] = None,
    cache_pos: Optional[jax.Array] = None,
    cache_lanes: Optional[jax.Array] = None,
    head_mode: str = "full",  # 'full' | 'last' | 'none'
) -> Tuple[jax.Array, Optional[Dict], Dict]:
    """Returns (logits-or-hidden, new_cache (if cache given), aux losses).

    ``cache_pos`` is ``()`` (every lane starts there) or ``(B,)`` (one
    start per lane: a server decodes slots at different positions in one
    call).  ``cache_lanes`` ((B,) bool) limits the cache update to those
    batch lanes: the others keep their KV and recurrent state unchanged."""
    x = _embed_inputs(cfg, params, batch)
    x = constrain(x, "hidden")
    B, S, _ = x.shape
    pos0 = jnp.zeros((), jnp.int32) if cache_pos is None else cache_pos
    positions = (jnp.reshape(pos0, (-1, 1)) + jnp.arange(S)).astype(jnp.int32)
    positions = jnp.broadcast_to(positions, (B, S))
    L = cfg.num_layers

    new_cache: Optional[Dict] = None

    if cfg.family == "ssm":
        use_cache = cache is not None

        def body(carry, layer_and_st):
            h, aux_acc = carry
            if use_cache:
                layer, st = layer_and_st
            else:
                layer, st = layer_and_st, None
            h, new_st = _rwkv_layer_body(cfg, layer, h, st)
            if use_cache:
                new_st = _keep_lanes(cache_lanes, st, new_st)
            return (h, aux_acc), (new_st if use_cache else 0)

        if cfg.remat:
            body = _maybe_checkpoint(cfg, body)
        xs = (params["layers"], cache["rwkv"]) if use_cache else params["layers"]
        (x, _), new_sts = _scan_or_loop(body, (x, ZERO_AUX()), xs, L, cfg.scan_layers)
        if use_cache:
            new_cache = {"rwkv": new_sts}
        aux = ZERO_AUX()

    elif cfg.family == "hybrid":
        every = cfg.hybrid_attn_every
        shared = params["shared_block"]
        use_cache = cache is not None
        sk = cache["shared_k"] if use_cache else None
        sv = cache["shared_v"] if use_cache else None

        def body(carry, xs):
            h, aux_acc, sk, sv = carry
            layer, st, idx = xs
            m, new_st = mamba2_block(
                layer["mamba"], cfg, rmsnorm(layer["ln1"], h, cfg.norm_eps),
                state=st if use_cache else None,
            )
            if use_cache:
                new_st = _keep_lanes(cache_lanes, st, new_st)
            h = h + m

            def run_shared(h, sk, sv):
                slot = idx // every
                if use_cache:
                    kv = (
                        jax.lax.dynamic_index_in_dim(sk, slot, 0, keepdims=False),
                        jax.lax.dynamic_index_in_dim(sv, slot, 0, keepdims=False),
                    )
                else:
                    kv = None
                a, new_kv = attention(
                    shared["attn"], cfg, rmsnorm(shared["ln1"], h, cfg.norm_eps),
                    positions=positions, kv_cache=kv, cache_pos=pos0,
                    cache_lanes=cache_lanes,
                )
                h2 = h + a
                h2 = h2 + mlp(shared["ffn"], cfg,
                              rmsnorm(shared["ln2"], h2, cfg.norm_eps))
                if use_cache:
                    sk = jax.lax.dynamic_update_index_in_dim(sk, new_kv[0], slot, 0)
                    sv = jax.lax.dynamic_update_index_in_dim(sv, new_kv[1], slot, 0)
                return h2, sk, sv

            is_shared = (idx % every) == (every - 1)
            h, sk, sv = jax.lax.cond(
                is_shared, run_shared, lambda h, a, b: (h, a, b), h, sk, sv
            )
            h = constrain(h, "hidden")
            return (h, aux_acc, sk, sv), (new_st if use_cache else 0)

        if cfg.remat:
            body = _maybe_checkpoint(cfg, body)
        if use_cache:
            sts = cache["mamba"]
        else:
            sts = jnp.zeros((L,), x.dtype)  # per-layer placeholder
            sk = jnp.zeros((1,), x.dtype)  # placeholders threaded through carry
            sv = jnp.zeros((1,), x.dtype)
        (x, _, sk, sv), new_sts = _scan_or_loop(
            body, (x, ZERO_AUX(), sk, sv),
            (params["layers"], sts, jnp.arange(L)), L, cfg.scan_layers,
        )
        if use_cache:
            new_cache = {"mamba": new_sts, "shared_k": sk, "shared_v": sv}
        aux = ZERO_AUX()

    else:  # attention families: dense / moe / vlm / audio
        use_cache = cache is not None

        def body(carry, xs):
            h, aux_acc = carry
            layer, kv = xs
            h, new_kv, aux = _attn_layer_body(
                cfg, layer, h, positions, kv if use_cache else None, pos0,
                cache_lanes,
            )
            aux_acc = jax.tree.map(lambda a, b: a + b, aux_acc, aux)
            return (h, aux_acc), (new_kv if use_cache else 0)

        if cfg.remat:
            body = _maybe_checkpoint(cfg, body)
        kvs = (cache["k"], cache["v"]) if use_cache else _dummy_kv(cfg, B, L, x.dtype)
        (x, aux), new_kvs = _scan_or_loop(
            body, (x, ZERO_AUX()), (params["layers"], kvs), L, cfg.scan_layers
        )
        if use_cache:
            new_cache = {"k": new_kvs[0], "v": new_kvs[1]}

    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    x = constrain(x, "hidden")
    if head_mode == "none":
        # chunked-loss / prefill paths apply the head themselves
        return x, new_cache, aux
    if head_mode == "last":
        x = x[:, -1:]
    logits = constrain(apply_head(cfg, params, x), "logits")
    logits = logits[..., : cfg.vocab_size]  # drop vocab padding
    if head_mode == "last":
        logits = logits[:, 0]
    return logits, new_cache, aux


def _dummy_kv(cfg, B, L, dtype):
    # zero-length KV slots so train/prefill scans have uniform xs structure
    shape = (L, B, cfg.num_kv_heads, 0, cfg.head_dim)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def _dummy_rwkv_states(cfg, B, dtype, L):
    hd = cfg.ssm.head_dim
    H = cfg.d_model // hd
    return {
        "tmix_x": jnp.zeros((L, B, 0), dtype),
        "cmix_x": jnp.zeros((L, B, 0), dtype),
        "wkv": jnp.zeros((L, B, 0, hd, hd), jnp.float32),
    }


def _dummy_mamba_states(cfg, B, dtype, L):
    return {
        "conv": jnp.zeros((L, B, 0, 1), dtype),
        "ssm": jnp.zeros((L, B, 0, 1, 1), jnp.float32),
    }


# --------------------------------------------------------------------------
# training loss / decode step
# --------------------------------------------------------------------------
def _chunked_xent(cfg: ModelConfig, params: Dict, hidden: jax.Array,
                  labels: jax.Array) -> jax.Array:
    """Sequence-chunked cross entropy: the (B, S, V) logits tensor is never
    materialized — each scan step computes a (B, chunk, V_padded) slab,
    reduces it to per-token log-likelihoods, and drops it."""
    B, S, d = hidden.shape
    chunk = min(cfg.loss_chunk, S)
    while S % chunk:
        chunk -= 1  # largest divisor <= loss_chunk
    n = S // chunk
    hs = jnp.moveaxis(hidden.reshape(B, n, chunk, d), 1, 0)  # (n,B,chunk,d)
    ls = jnp.moveaxis(labels.reshape(B, n, chunk), 1, 0)
    Vp, V = cfg.padded_vocab, cfg.vocab_size

    @jax.checkpoint  # recompute the logits slab in bwd: O(B*chunk*V) -> O(1)
    def step(acc, inp):
        h, lab = inp
        logits = apply_head(cfg, params, h)  # (B, chunk, Vp) f32
        if Vp != V:
            col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
            logits = jnp.where(col < V, logits, -1e30)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, lab[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(ll), 0

    total, _ = jax.lax.scan(step, jnp.zeros((), jnp.float32), (hs, ls))
    return -total / (B * S)


def loss_fn(cfg: ModelConfig, params: Dict, batch: Dict) -> Tuple[jax.Array, Dict]:
    hidden, _, aux = forward(cfg, params, batch, head_mode="none")
    labels = batch["labels"]
    ce = _chunked_xent(cfg, params, hidden, labels)
    total = ce
    metrics = {"ce": ce}
    if cfg.moe is not None:
        lb = aux["load_balance"] / cfg.num_layers
        rz = aux["router_z"] / cfg.num_layers
        total = total + 0.01 * lb + cfg.moe.router_z_loss * rz
        metrics.update(load_balance=lb, router_z=rz)
    metrics["loss"] = total
    return total, metrics


def decode_step(
    cfg: ModelConfig,
    params: Dict,
    state: Dict,
    tokens: jax.Array,  # (B, 1)
    cache_pos: jax.Array,  # () or (B,)
    cache_lanes: Optional[jax.Array] = None,  # (B,) bool lanes to update
) -> Tuple[jax.Array, Dict]:
    """One token of autoregressive decode against the serve state.

    ``cache_pos`` is the position each lane's token takes: ``()`` for all
    lanes, or ``(B,)``, one per lane, so lanes at different positions
    decode in one call (RoPE, the cache write and the key mask each at the
    lane's own position)."""
    logits, new_cache, _ = forward(
        cfg, params, {"tokens": tokens}, cache=state, cache_pos=cache_pos,
        cache_lanes=cache_lanes,
    )
    return logits[:, -1], new_cache


# --------------------------------------------------------------------------
# explicit-TP transformer block (context collectives)
# --------------------------------------------------------------------------

_TP_COL = frozenset({"wq", "wk", "wv", "gate", "up"})   # column-parallel
_TP_ROW = frozenset({"wo", "down"})                     # row-parallel


def _tp_local_cfg(cfg: ModelConfig, n: int) -> ModelConfig:
    if cfg.num_heads % n or cfg.num_kv_heads % n:
        raise ValueError(
            f"TP over {n} devices needs num_heads ({cfg.num_heads}) and "
            f"num_kv_heads ({cfg.num_kv_heads}) divisible by it")
    import dataclasses

    return dataclasses.replace(
        cfg, num_heads=cfg.num_heads // n, num_kv_heads=cfg.num_kv_heads // n)


def transformer_block_tp(
    layer: Dict,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d) replicated; SP: (B, S_local, d) seq shards
    *,
    positions: jax.Array,  # (B, S) — full sequence in both variants
    ctx=None,
    sequence_parallel: bool = False,
    seq_axis: int = 1,
) -> jax.Array:
    """The full explicit-TP transformer block (inside shard_map), running
    entirely on context collectives (``repro.comms.api``) — the shard_map
    counterpart of the GSPMD block (``transformer_block_ref``).

    ``layer`` holds this shard's TP slices (``tp_block_specs`` gives the
    matching shard_map in_specs): QKV and gate/up column-parallel, wo/down
    row-parallel, norms replicated.

    * **TP** (default): activations replicated; attention runs on the
      local heads, and both combine points are context-planned staged
      all-reduces.
    * **SP** (``sequence_parallel=True``): activations arrive
      sequence-sharded; the QKV projections share ONE context-planned
      all-gather (``api.allgather_matmul`` — each gathered block projected
      the hop it lands), and both combines return to sequence shards via
      just-in-time ``api.matmul_reduce_scatter``.

    All mode/chunking/fusion/stage-order decisions come from the active
    :func:`repro.comms.api.comm_context` (or the explicit ``ctx``) — no
    per-call comms plumbing.
    """
    from ..comms import api

    c = ctx if ctx is not None else api.current_context()
    names = c._names(None)
    n = math.prod(jax.lax.axis_size(a) for a in names)
    lcfg = _tp_local_cfg(cfg, n)
    ap = layer["attn"]

    h = rmsnorm(layer["ln1"], x, cfg.norm_eps)
    if sequence_parallel:
        hg, (q, k, v) = api.allgather_matmul(
            h, (ap["wq"]["w"], ap["wk"]["w"], ap["wv"]["w"]),
            axis=seq_axis, ctx=c,
        )
        # biases stay out of the fused ring: added once to the projections
        if "b" in ap["wq"]:
            q, k, v = q + ap["wq"]["b"], k + ap["wk"]["b"], v + ap["wv"]["b"]
        heads, _ = attention_heads(
            ap, lcfg, hg, positions=positions, qkv=(q, k, v))
        x = x + attention_tp_out_sp(ap, heads, seq_axis=seq_axis, ctx=c)
        h2 = rmsnorm(layer["ln2"], x, cfg.norm_eps)
        return x + ffn_apply_tp_sp(layer["ffn"], h2, seq_axis=seq_axis, ctx=c)

    heads, _ = attention_heads(ap, lcfg, h, positions=positions)
    x = x + attention_tp_out(ap, heads, ctx=c)
    h2 = rmsnorm(layer["ln2"], x, cfg.norm_eps)
    return x + ffn_apply_tp(layer["ffn"], h2, ctx=c)


def transformer_block_ref(
    layer: Dict,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    *,
    positions: jax.Array,
) -> jax.Array:
    """The same block on full (unsharded) parameters — the GSPMD path:
    under jit with TP shardings the partitioner emits the collectives this
    module's explicit form issues by hand."""
    h, _ = attention(
        layer["attn"], cfg, rmsnorm(layer["ln1"], x, cfg.norm_eps),
        positions=positions,
    )
    x = x + h
    return x + ffn_apply(layer["ffn"], rmsnorm(layer["ln2"], x, cfg.norm_eps))


def tp_block_specs(layer: Dict, axis_names, *, sequence_parallel: bool = False):
    """(x_spec, layer_specs) PartitionSpecs for running
    ``transformer_block_tp`` under shard_map (or as GSPMD in_shardings for
    the reference block): QKV/gate/up column-parallel over ``axis_names``,
    wo/down row-parallel, everything else replicated; ``x`` replicated (TP)
    or sequence-sharded (SP)."""
    from jax.sharding import PartitionSpec as P

    names = tuple(axis_names)

    def leaf_spec(path, leaf):
        keys = [k.key for k in path if hasattr(k, "key")]
        proj = next((k for k in keys if k in _TP_COL | _TP_ROW), None)
        if proj in _TP_COL:
            return P(None, names) if keys[-1] == "w" else P(names)
        if proj in _TP_ROW:
            return P(names, None) if keys[-1] == "w" else P()
        return P()

    specs = jax.tree_util.tree_map_with_path(leaf_spec, layer)
    x_spec = P(None, names, None) if sequence_parallel else P()
    return x_spec, specs
