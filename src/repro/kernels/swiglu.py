"""Pallas-TPU fused SwiGLU kernel: out = silu(gate) * up.

2-D blocked elementwise kernel: (block_rows, block_cols) VMEM tiles, f32
silu, output in the input dtype.  Fusing the two reads + activation into one
pass halves HBM traffic vs. separate silu/mul ops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["swiglu_pallas"]


def _swiglu_kernel(g_ref, u_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)
    u = u_ref[...].astype(jnp.float32)
    o_ref[...] = (g * jax.lax.logistic(g) * u).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_cols", "interpret"))
def swiglu_pallas(
    gate: jax.Array,
    up: jax.Array,
    *,
    block_rows: int = 256,
    block_cols: int = 512,
    interpret: bool = False,
) -> jax.Array:
    assert gate.shape == up.shape, (gate.shape, up.shape)
    orig_shape = gate.shape
    d = gate.shape[-1]
    rows = gate.size // d
    g2, u2 = gate.reshape(rows, d), up.reshape(rows, d)

    bc = min(block_cols, d)
    br = min(block_rows, rows) or 1
    pad_r, pad_c = (-rows) % br, (-d) % bc
    if pad_r or pad_c:
        g2 = jnp.pad(g2, ((0, pad_r), (0, pad_c)))
        u2 = jnp.pad(u2, ((0, pad_r), (0, pad_c)))
    grid = (g2.shape[0] // br, g2.shape[1] // bc)

    out = pl.pallas_call(
        _swiglu_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
            pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(g2.shape, gate.dtype),
        interpret=interpret,
        name="swiglu",
    )(g2, u2)
    if pad_r or pad_c:
        out = out[:rows, :d]
    return out.reshape(orig_shape)
