"""Pure-jnp oracles for every Pallas kernel (the correctness ground truth).

These are also the default backend on CPU and the VJP bodies for the
custom-vjp kernel wrappers (kernel forward, ref backward).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["rmsnorm", "swiglu", "flash_attention", "flash_attention_chunked",
           "rwkv6_scan", "mamba2_ssd_scan"]


def mamba2_ssd_scan(
    x: jax.Array,  # (B, S, H, P)
    Bmat: jax.Array,  # (B, S, N)
    Cmat: jax.Array,  # (B, S, N)
    decay: jax.Array,  # (B, S, H) = exp(dt * A)
    dt: jax.Array,  # (B, S, H)
    state: Optional[jax.Array] = None,  # (B, H, P, N)
    remat_chunk: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Mamba2 SSD recurrence (the inner loop of models.mamba2):

        h_t = decay_t * h_{t-1} + dt_t * (x_t B_t^T)
        y_t = h_t C_t

    Returns (y: (B,S,H,P) f32, final_state: (B,H,P,N) f32).  With
    ``remat_chunk`` dividing S (and below it) the backward pass keeps the
    state at chunk boundaries only and recomputes inside each chunk:
    O(S/chunk) residuals instead of O(S), the same numbers.
    """
    B, S, H, P = x.shape
    N = Bmat.shape[-1]
    s0 = (jnp.zeros((B, H, P, N), jnp.float32) if state is None
          else state.astype(jnp.float32))

    def step(h, inp):
        xt, Bt, Ct, dct, dtt = inp
        upd = dtt[..., None, None] * (
            xt.astype(jnp.float32)[..., :, None]
            * Bt.astype(jnp.float32)[:, None, None, :]
        )
        h = dct[..., None, None] * h + upd
        yt = jnp.einsum("bhpn,bn->bhp", h, Ct.astype(jnp.float32))
        return h, yt

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(Bmat, 1, 0),
          jnp.moveaxis(Cmat, 1, 0), jnp.moveaxis(decay, 1, 0),
          jnp.moveaxis(dt, 1, 0))
    c = remat_chunk
    if c and S % c == 0 and S > c:
        @jax.checkpoint
        def chunk_body(h, inp):
            return jax.lax.scan(step, h, inp)

        resh = lambda a: a.reshape((S // c, c) + a.shape[1:])
        h_final, ys = jax.lax.scan(chunk_body, s0, tuple(map(resh, xs)))
        ys = ys.reshape((S,) + ys.shape[2:])
    else:
        h_final, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1), h_final


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    x32 = x.astype(jnp.float32)
    rms = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * rms).astype(x.dtype) * scale


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    g32 = gate.astype(jnp.float32)
    return (jax.nn.silu(g32) * up.astype(jnp.float32)).astype(gate.dtype)


def flash_attention(
    q: jax.Array,  # (B, H, S, hd)
    k: jax.Array,  # (B, Hkv, T, hd)
    v: jax.Array,  # (B, Hkv, T, hd)
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,  # (B, T) valid-key mask
) -> jax.Array:
    B, H, S, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    rep = H // Hkv
    kx = jnp.repeat(k, rep, axis=1)
    vx = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, kx).astype(jnp.float32) * scale
    if causal:
        qpos = jnp.arange(S)[:, None] + (T - S)  # allow cached prefix
        kpos = jnp.arange(T)[None, :]
        logits = jnp.where(kpos <= qpos, logits, -jnp.inf)
    if kv_mask is not None:
        logits = jnp.where(kv_mask[:, None, None, :], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", probs.astype(v.dtype), vx)
    return out


def flash_attention_chunked(
    q: jax.Array,  # (B, H, S, hd)
    k: jax.Array,  # (B, Hkv, T, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,
    chunk: int = 1024,
) -> jax.Array:
    """Online-softmax attention scanned over key chunks: O(S*chunk) live
    memory instead of the O(S*T) logits tensor.  Pure jnp — this is what the
    Pallas kernel computes, in a form every backend can lower (the dry-run
    and non-TPU training path); numerically identical to `flash_attention`.
    """
    B, H, S, hd = q.shape
    Hkv, T = k.shape[1], k.shape[2]
    if T % chunk or T <= chunk:
        return flash_attention(q, k, v, causal=causal, scale=scale, kv_mask=kv_mask)
    scale = scale if scale is not None else 1.0 / (hd ** 0.5)
    rep = H // Hkv
    n = T // chunk
    cq = chunk if (S % chunk == 0 and S > chunk) else S  # query chunk
    nq = S // cq

    kc = jnp.moveaxis(k.reshape(B, Hkv, n, chunk, hd), 2, 0)  # (n,B,Hkv,c,hd)
    vc = jnp.moveaxis(v.reshape(B, Hkv, n, chunk, hd), 2, 0)
    mc = (jnp.moveaxis(kv_mask.reshape(B, n, chunk), 1, 0)
          if kv_mask is not None else jnp.zeros((n, 0)))
    qc = jnp.moveaxis(q.reshape(B, H, nq, cq, hd), 2, 0)  # (nq,B,H,cq,hd)

    def q_block(inp):
        qi, i = inp  # (B,H,cq,hd), scalar q-chunk index
        q32 = qi.astype(jnp.float32)
        qpos = i * cq + jnp.arange(cq)[:, None] + (T - S)

        @jax.checkpoint
        def body(carry, kvm):
            m, l, acc, j = carry
            kj, vj, mj = kvm
            kj = jnp.repeat(kj.astype(jnp.float32), rep, axis=1)  # (B,H,c,hd)
            vj = jnp.repeat(vj.astype(jnp.float32), rep, axis=1)
            s = jnp.einsum("bhsd,bhtd->bhst", q32, kj) * scale
            kpos = j * chunk + jnp.arange(chunk)[None, :]
            if causal:
                s = jnp.where((kpos <= qpos)[None, None], s, -jnp.inf)
            if kv_mask is not None:
                s = jnp.where(mj[:, None, None, :], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum("bhst,bhtd->bhsd", p, vj)
            return (m_new, l, acc, j + 1), 0

        m0 = jnp.full((B, H, cq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        acc0 = jnp.zeros((B, H, cq, hd), jnp.float32)
        (m, l, acc, _), _ = jax.lax.scan(
            body, (m0, l0, acc0, jnp.zeros((), jnp.int32)), (kc, vc, mc)
        )
        l = jnp.where(l == 0.0, 1.0, l)
        return (acc / l[..., None]).astype(q.dtype)

    out = jax.lax.map(q_block, (qc, jnp.arange(nq)))  # (nq,B,H,cq,hd)
    return jnp.moveaxis(out, 0, 2).reshape(B, H, S, hd)


def rwkv6_scan(
    r: jax.Array,  # (B, H, S, hd)
    k: jax.Array,  # (B, H, S, hd)
    v: jax.Array,  # (B, H, S, hd)
    w: jax.Array,  # (B, H, S, hd) decay in (0,1), data-dependent
    u: jax.Array,  # (H, hd) bonus for the current token
    state: Optional[jax.Array] = None,  # (B, H, hd, hd)
) -> Tuple[jax.Array, jax.Array]:
    """WKV6 linear-attention recurrence (Finch, arXiv:2404.05892).

        y_t = r_t @ (S_t + diag(u) k_t v_t^T)
        S_{t+1} = diag(w_t) S_t + k_t v_t^T

    Returns (y: (B,H,S,hd), final_state: (B,H,hd,hd)); math in f32.
    """
    B, H, S, hd = r.shape
    r32, k32, v32, w32 = (a.astype(jnp.float32) for a in (r, k, v, w))
    u32 = u.astype(jnp.float32)
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32) if state is None else state.astype(jnp.float32)

    def step(s, inp):
        rt, kt, vt, wt = inp  # (B,H,hd)
        kv = kt[..., :, None] * vt[..., None, :]  # (B,H,hd_k,hd_v)
        y = jnp.einsum("bhi,bhij->bhj", rt, s + u32[None, :, :, None] * kv)
        s = wt[..., :, None] * s + kv
        return s, y

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (r32, k32, v32, w32))
    s_final, ys = jax.lax.scan(step, s0, xs)
    y = jnp.moveaxis(ys, 0, 2)  # (B,H,S,hd)
    return y.astype(r.dtype), s_final
