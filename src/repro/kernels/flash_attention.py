"""Pallas-TPU blocked (flash) attention kernel, causal or full, with GQA.

Grid: (B*H, S/bq, T/bk) — the kv dimension is the innermost (sequential)
axis; online-softmax running max/denominator/accumulator live in VMEM
scratch that persists across kv steps.  Causal q-blocks skip kv-blocks
entirely above the diagonal (the pl.when guard), which is where the 2x
flop win over naive masking comes from.

Block sizes default to 128x128 (MXU-aligned); q/k/v tiles + f32 accumulator
for (bq=128, bk=128, hd<=128) stay well under 2 MB of VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, bq: int, bk: int,
):
    i = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: kv block j only contributes if its first key position is not
    # strictly below the q block's last query position
    live = (j * bk <= (i + 1) * bq - 1) if causal else (j >= 0)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)  # (bk, hd)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(kpos <= qpos, s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )

    @pl.when(j == nk - 1)
    def _finish():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> zeros
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # (B, H, S, hd)
    k: jax.Array,  # (B, Hkv, T, hd)
    v: jax.Array,  # (B, Hkv, T, hd)
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, S, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    assert H % Hkv == 0, (H, Hkv)
    rep = H // Hkv
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)

    bq, bk = min(block_q, S), min(block_k, T)
    pad_q, pad_k = (-S) % bq, (-T) % bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0))) if pad_q else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0))) if pad_k else v
    Sp, Tp = S + pad_q, T + pad_k
    # padded keys must never win the softmax: causal masking covers q-pads;
    # for key pads rely on causal structure (Tp-pads are masked for all real
    # queries when causal). For non-causal, mask via scores: handled by
    # padding k with +0 but masking in-kernel needs kpos<T — fold into causal
    # path or accept only T % bk == 0 for non-causal:
    if not causal and pad_k:
        raise ValueError("non-causal flash kernel requires T % block_k == 0")

    qf = qp.reshape(B * H, Sp, hd)
    kf = kp.reshape(B * Hkv, Tp, hd)
    vf = vp.reshape(B * Hkv, Tp, hd)

    def q_map(b, i, j):
        return (b, i, 0)

    def kv_map(b, i, j):
        return ((b // H) * Hkv + (b % H) // rep, j, 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, bq=bq, bk=bk
        ),
        grid=(B * H, Sp // bq, Tp // bk),
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bk, hd), kv_map),
            pl.BlockSpec((1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    out = out.reshape(B, H, Sp, hd)
    return out[:, :, :S] if pad_q else out
