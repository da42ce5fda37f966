"""Pallas-TPU WKV6 recurrence kernel (RWKV6 time-mix inner loop).

Grid: (B*H, S/chunk) — the time dimension is the sequential axis; the
(hd x hd) f32 recurrent state lives in VMEM scratch and persists across
chunks.  Within a chunk the recurrence is a fori_loop over time steps on
VMEM-resident (chunk, hd) tiles: HBM sees each element exactly once.

This is the TPU-native replacement for the CUDA wkv kernel the RWKV project
ships: the hd=64 head fits a (64, 64) state tile; the per-step outer
products k_t v_t^T map to (64x64) VPU/MXU ops.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rwkv6_scan_pallas"]


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
                 state_scr, r_scr, k_scr, v_scr, w_scr, y_scr, *, chunk: int):
    c = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)

    # stage the chunk in f32 VMEM: each time step loads its (1, hd) rows
    # through the ref (the TPU lowering has no dynamic slice of a value)
    for src, dst in ((r_ref, r_scr), (k_ref, k_scr), (v_ref, v_scr),
                     (w_ref, w_scr)):
        dst[...] = src[0].astype(jnp.float32)
    hd = state_scr.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (hd, hd), 1)
           ).astype(jnp.float32)

    def col(row):  # (1, hd) -> (hd, 1), exact, by a lane reduction
        return jnp.sum(eye * row, axis=1, keepdims=True)

    u = col(u_ref[0].astype(jnp.float32))

    def step(t, s):
        row = pl.ds(t, 1)
        kv = col(k_scr[row, :]) * v_scr[row, :]  # (hd, hd) outer product
        y_scr[row, :] = jnp.sum(col(r_scr[row, :]) * (s + u * kv), axis=0,
                                keepdims=True)
        return col(w_scr[row, :]) * s + kv

    state_scr[...] = jax.lax.fori_loop(0, chunk, step, state_scr[...])
    y_ref[0] = y_scr[...].astype(y_ref.dtype)

    @pl.when(c == nc - 1)
    def _emit_state():
        sT_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_scan_pallas(
    r: jax.Array,  # (B, H, S, hd)
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,  # (H, hd)
    state: Optional[jax.Array] = None,  # (B, H, hd, hd)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, H, S, hd = r.shape
    ch = min(chunk, S)
    if S % ch:
        raise ValueError(f"S={S} must be a multiple of chunk={ch}")
    s0 = state if state is not None else jnp.zeros((B, H, hd, hd), jnp.float32)

    rf, kf, vf, wf = (a.reshape(B * H, S, hd) for a in (r, k, v, w))
    # (B*H, 1, hd): a (1, 1, hd) block spans the array's last two dims, as
    # the TPU lowering requires of blocks not (8, 128)-aligned
    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)
    s0f = s0.reshape(B * H, hd, hd).astype(jnp.float32)

    def t_map(b, c):
        return (b, c, 0)

    def s_map(b, c):
        return (b, 0, 0)

    y, sT = pl.pallas_call(
        functools.partial(_wkv6_kernel, chunk=ch),
        grid=(B * H, S // ch),
        in_specs=[
            pl.BlockSpec((1, ch, hd), t_map),
            pl.BlockSpec((1, ch, hd), t_map),
            pl.BlockSpec((1, ch, hd), t_map),
            pl.BlockSpec((1, ch, hd), t_map),
            pl.BlockSpec((1, 1, hd), s_map),
            pl.BlockSpec((1, hd, hd), s_map),
        ],
        out_specs=[
            pl.BlockSpec((1, ch, hd), t_map),
            pl.BlockSpec((1, hd, hd), s_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, hd), r.dtype),
            jax.ShapeDtypeStruct((B * H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)]
        + [pltpu.VMEM((ch, hd), jnp.float32)] * 5,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="rwkv6_scan",
    )(rf, kf, vf, wf, uf, s0f)
    return y.reshape(B, H, S, hd), sT.reshape(B, H, hd, hd)
