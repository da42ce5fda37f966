"""Pallas-TPU RMSNorm kernel.

Row-blocked: each grid step normalizes ``block_rows`` rows of the flattened
(rows, d) input entirely in VMEM.  d is padded by the wrapper to a multiple
of 128 (lane width); accumulation in f32.

VMEM budget: block_rows * d * (in + out + f32 temp) — with the default
block_rows=256 and d=8192 that is ~12 MB < 16 MB v5e VMEM; the wrapper
shrinks block_rows for wider models.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["rmsnorm_pallas"]


def _rmsnorm_kernel(x_ref, scale_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    scale = scale_ref[...].astype(jnp.float32)
    o_ref[...] = (y * scale[None, :]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm_pallas(
    x: jax.Array,
    scale: jax.Array,
    *,
    eps: float = 1e-5,
    block_rows: int = 256,
    interpret: bool = False,
) -> jax.Array:
    orig_shape = x.shape
    d = x.shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)

    # shrink the row block until the VMEM working set is comfortable (~12MB)
    while block_rows > 8 and block_rows * d * 12 > 12 * 2**20:
        block_rows //= 2
    pad_rows = (-rows) % block_rows
    if pad_rows:
        x2 = jnp.pad(x2, ((0, pad_rows), (0, 0)))
    grid = (x2.shape[0] // block_rows,)

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2, scale)
    if pad_rows:
        out = out[:rows]
    return out.reshape(orig_shape)
