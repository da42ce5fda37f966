"""GQA attention block: RoPE, optional qk-norm / QKV bias, KV cache.

Prefill/train run the flash path (`kernels.ops.flash_attention`); decode
attends one query against the full padded cache with a position mask —
when the KV cache is sequence-sharded the caller wraps this in the
sharded-KV combine (`serving.sharded_decode_attention`).
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..comms import api
from ..configs.base import ModelConfig
from ..kernels import ops
from .layers import apply_rope, dense, dense_init, rmsnorm, rmsnorm_init

__all__ = ["attn_init", "attention", "attention_heads", "attention_tp_out",
           "attention_tp_out_sp"]


def attn_init(key, cfg: ModelConfig, *, dtype) -> Dict:
    ks = jax.random.split(key, 6)
    out_scale = 0.02 / (2 * cfg.num_layers) ** 0.5
    p = {
        "wq": dense_init(ks[0], cfg.d_model, cfg.q_dim, dtype=dtype, bias=cfg.qkv_bias),
        "wk": dense_init(ks[1], cfg.d_model, cfg.kv_dim, dtype=dtype, bias=cfg.qkv_bias),
        "wv": dense_init(ks[2], cfg.d_model, cfg.kv_dim, dtype=dtype, bias=cfg.qkv_bias),
        "wo": dense_init(ks[3], cfg.q_dim, cfg.d_model, dtype=dtype, scale=out_scale),
    }
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(cfg.head_dim, dtype=dtype)
        p["k_norm"] = rmsnorm_init(cfg.head_dim, dtype=dtype)
    return p


def attention_heads(
    p: Dict,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    *,
    positions: jax.Array,  # (B, S) absolute positions
    kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,  # (B,Hkv,T,hd) x2
    cache_pos: Optional[jax.Array] = None,  # () or (B,) position written
    cache_lanes: Optional[jax.Array] = None,  # (B,) bool lanes to write
    qkv: Optional[Tuple[jax.Array, jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """Everything up to (but not including) the output projection: QKV,
    RoPE, flash/decode attention.  Returns the (B, S, H*hd) head outputs —
    the explicit-TP block projects + combines them through the context
    (``attention_tp_out``/``_sp``), the GSPMD path via ``p["wo"]``.

    ``qkv`` optionally supplies precomputed (pre-reshape) projections —
    the SP path computes them fused with the sequence all-gather
    (``api.allgather_matmul``) and hands them in here.

    ``cache_pos`` is one position for every lane (``()``) or one per lane
    (``(B,)``: each lane writes its block and masks its keys at its own
    position).  ``cache_lanes`` limits the cache write to those batch
    lanes; the other lanes keep what the cache holds at ``cache_pos`` (a
    select over the written block only, not over the cache).
    """
    B, S, _ = x.shape
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    if qkv is None:
        q, k, v = dense(p["wq"], x), dense(p["wk"], x), dense(p["wv"], x)
    else:
        q, k, v = qkv
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, Hkv, hd)
    v = v.reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    qh = q.transpose(0, 2, 1, 3)  # (B,H,S,hd)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)

    if kv_cache is None:
        out = ops.flash_attention(qh, kh, vh, causal=cfg.causal)
        new_cache = None
    else:
        ck, cv = kv_cache  # (B, Hkv, T, hd)
        ck = _cache_write(ck, kh.astype(ck.dtype), cache_pos, cache_lanes)
        cv = _cache_write(cv, vh.astype(cv.dtype), cache_pos, cache_lanes)
        new_cache = (ck, cv)
        if S > 1:
            # prefill: the new block is the whole context — attend causally
            # within it; the cache write above is just state installation
            out = ops.flash_attention(qh, kh, vh, causal=cfg.causal)
        else:
            # decode: one query against the valid prefix of each lane's cache
            T = ck.shape[2]
            valid = jnp.arange(T)[None, :] <= jnp.reshape(cache_pos, (-1, 1))
            valid = jnp.broadcast_to(valid, (B, T))
            out = ops.flash_attention(qh, ck, cv, causal=False, kv_mask=valid)

    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    return out, new_cache


def _cache_write(c: jax.Array, new: jax.Array, pos: jax.Array,
                 lanes: Optional[jax.Array]) -> jax.Array:
    """``new`` (B, Hkv, S, hd) written into the cache ``c`` (B, Hkv, T, hd)
    at ``pos``: ``()`` for every lane, or ``(B,)`` one per lane.  Lanes not
    set in ``lanes`` keep what ``c`` holds.

    Every write is one update slice of all lanes at one position, with a
    select over the written block only (never over the cache): per-lane
    positions make one such write per lane, each taking its own lane's new
    rows and the other lanes' rows as they stand.  XLA keeps the cache's
    layout through these; a scatter of B rows (``vmap`` of the update)
    moves it and adds a layout copy of the cache in every layer."""
    if jnp.ndim(pos) == 0:
        writes = [(pos, lanes)]
    else:
        lane = jnp.arange(c.shape[0])
        writes = [(pos[b], lane == b if lanes is None else (lane == b) & lanes)
                  for b in range(c.shape[0])]
    for p, pick in writes:
        at = (0, 0, p, 0)
        block = new if pick is None else jnp.where(
            pick[:, None, None, None], new,
            jax.lax.dynamic_slice(c, at, new.shape))
        c = jax.lax.dynamic_update_slice(c, block, at)
    return c


def attention(
    p: Dict,
    cfg: ModelConfig,
    x: jax.Array,  # (B, S, d)
    *,
    positions: jax.Array,  # (B, S) absolute positions
    kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,  # (B,Hkv,T,hd) x2
    cache_pos: Optional[jax.Array] = None,  # () or (B,) position written
    cache_lanes: Optional[jax.Array] = None,  # (B,) bool lanes to write
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    out, new_cache = attention_heads(
        p, cfg, x, positions=positions, kv_cache=kv_cache, cache_pos=cache_pos,
        cache_lanes=cache_lanes,
    )
    return dense(p["wo"], out), new_cache


def attention_tp_out(
    p: Dict,
    out_local: jax.Array,  # (B, S, local_q_dim) — this shard's heads
    axis_names: Optional[Sequence[str]] = None,
    *,
    num_chunks: Optional[int] = None,
    ctx=None,
) -> jax.Array:
    """Explicit tensor-parallel output projection (inside shard_map).

    Heads are sharded over the context axes; ``p["wo"]`` holds the matching
    rows, so the local matmul is a partial sum over head shards.  The
    context-planned all-reduce combines the partials — the TP-reduction
    analogue of the OpTree all-gather, with the slow axes carrying only the
    scattered payload.  ``axis_names``/``num_chunks`` are legacy overrides.
    """
    partial = dense(p["wo"], out_local)
    return api.all_reduce(partial, axis=-1, ctx=ctx, axes=axis_names,
                          num_chunks=api.legacy_chunks(num_chunks))


def attention_tp_out_sp(
    p: Dict,
    out_local: jax.Array,  # (B, S, local_q_dim) — this shard's heads
    axis_names: Optional[Sequence[str]] = None,
    *,
    seq_axis: int = 1,
    fuse: object = None,
    links: Optional[Dict] = None,
    ctx=None,
) -> jax.Array:
    """Sequence-parallel TP output projection (inside shard_map).

    Like ``attention_tp_out`` but combining back to *sequence shards* (the
    SP residual-stream layout): ``psum_scatter(out_local @ wo)`` along
    ``seq_axis``, planned and (when the overlap model wins) fused per block
    by the context (``api.matmul_reduce_scatter`` — the wo block matmuls
    feed the ring just-in-time).  A wo bias, if present, is added once to
    the scattered output (never into the partial sums).
    """
    if ctx is None:
        ctx = api.legacy_context(axis_names, links)
    out = api.matmul_reduce_scatter(
        out_local, p["wo"]["w"], axis=seq_axis, axes=axis_names,
        ctx=ctx, fuse=fuse,
    )
    if "b" in p["wo"]:
        out = out + p["wo"]["b"]
    return out
