"""Mamba2 (SSD) mixer: zamba2's backbone and granite-4.0-h's Mamba layers.

State-space recurrence per head (P = head_dim, N = state_dim), B and C
shared by all heads (one group):

    h_t = exp(A * dt_t) * h_{t-1} + dt_t * (x_t  B_t^T)     h: (P, N)
    y_t = h_t C_t + D * x_t

with a width-4 causal depthwise conv (with bias) and silu on (x, B, C),
``dt = softplus(dt_raw + dt_bias)``, ``A = -exp(A_log)``, then the gated
RMSNorm ``rms(y * silu(z)) * w`` over the whole inner width (d_inner /
n_groups with one group) and the output projection.  Prompts run the scan
through ``kernels.ops.mamba2_scan`` (the Pallas kernel on the TPU); decode
is a one-step update of the carried {conv: (B, w-1, ch), ssm: (B, H, P, N)}
— O(1) per token.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..kernels import ops
from .layers import dense_init

__all__ = ["mamba2_init", "mamba2_block", "mamba2_state_init"]


def _dims(cfg: ModelConfig):
    d_in = cfg.ssm.expand * cfg.d_model
    P = cfg.ssm.head_dim
    H = d_in // P
    N = cfg.ssm.state_dim
    conv_ch = d_in + 2 * N
    return d_in, P, H, N, conv_ch


def mamba2_init(key, cfg: ModelConfig, *, dtype) -> Dict:
    d = cfg.d_model
    d_in, P, H, N, conv_ch = _dims(cfg)
    ks = jax.random.split(key, 5)
    out_scale = 0.02 / (2 * cfg.num_layers) ** 0.5
    return {
        "in_proj": dense_init(ks[0], d, 2 * d_in + 2 * N + H, dtype=dtype),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm.conv_dim, conv_ch)) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "a_log": jnp.zeros((H,), jnp.float32),  # A = -exp(a_log) = -1
        "d_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm_scale": jnp.ones((d_in,), dtype),
        "out_proj": dense_init(ks[2], d_in, d, dtype=dtype, scale=out_scale),
    }


def mamba2_state_init(cfg: ModelConfig, batch: int, *, dtype) -> Dict:
    d_in, P, H, N, conv_ch = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, cfg.ssm.conv_dim - 1, conv_ch), dtype),
        "ssm": jnp.zeros((batch, H, P, N), jnp.float32),
    }


def _causal_conv(
    xBC: jax.Array, w: jax.Array, b: jax.Array, conv_state: Optional[jax.Array]
) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv over time. xBC: (B,S,ch); w: (K,ch)."""
    K = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros_like(xBC[:, : K - 1])
    else:
        pad = conv_state.astype(xBC.dtype)
    xp = jnp.concatenate([pad, xBC], axis=1)  # (B, S+K-1, ch)
    out = sum(xp[:, i : i + xBC.shape[1]] * w[i] for i in range(K)) + b
    new_state = xp[:, -(K - 1) :]
    return out, new_state


def mamba2_block(
    p: Dict,
    cfg: ModelConfig,
    x: jax.Array,  # (B,S,d)
    *,
    state: Optional[Dict] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    B, S, d = x.shape
    d_in, P, H, N, conv_ch = _dims(cfg)

    zxbcdt = jnp.einsum("bsd,df->bsf", x, p["in_proj"]["w"])
    z, xBC, dt_raw = jnp.split(zxbcdt, [d_in, d_in + conv_ch], axis=-1)

    conv_state = state["conv"] if state is not None else None
    xBC, new_conv = _causal_conv(xBC, p["conv_w"], p["conv_b"], conv_state)
    xBC = jax.nn.silu(xBC.astype(jnp.float32)).astype(x.dtype)
    xs, Bmat, Cmat = jnp.split(xBC, [d_in, d_in + N], axis=-1)
    xs = xs.reshape(B, S, H, P)

    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    dA = dt * -jnp.exp(p["a_log"])  # log decay (B,S,H)

    if state is not None and S == 1:
        # decode: one step of the recurrence on the carried state
        h_final = (jnp.exp(dA[:, 0])[..., None, None] * state["ssm"]
                   + (dt[:, 0, :, None] * xs[:, 0].astype(jnp.float32))[..., None]
                   * Bmat[:, 0, None, None, :].astype(jnp.float32))
        y = jnp.einsum("bhpn,bn->bhp", h_final,
                       Cmat[:, 0].astype(jnp.float32))[:, None]
    else:
        s0 = state["ssm"] if state is not None else None
        y, h_final = ops.mamba2_scan(xs, Bmat, Cmat, dA, dt, s0,
                                     chunk=cfg.ssm.scan_chunk or 256)
    y = y + p["d_skip"][None, None, :, None] * xs.astype(jnp.float32)
    y = y.reshape(B, S, d_in) * jax.nn.silu(z.astype(jnp.float32))
    # gated RMSNorm over the inner width (one group), in float32
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.norm_eps)
    y = y.astype(x.dtype) * p["norm_scale"]
    out = jnp.einsum("bsf,fd->bsd", y, p["out_proj"]["w"])

    new_state = {"conv": new_conv, "ssm": h_final} if state is not None else None
    return out, new_state
