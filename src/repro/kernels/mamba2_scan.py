"""Pallas-TPU Mamba2 SSD recurrence kernel (zamba2's hot inner loop).

Grid: (B*H, S/chunk) — time is the sequential axis; the (P x N) f32
recurrent state lives in VMEM scratch and persists across chunks (same
structure as the WKV6 kernel: HBM reads each input element exactly once,
the state never leaves VMEM).

Per-(b,h) inputs are (S, P) x-tiles and (S, N) B/C tiles; B/C are shared
across heads, expressed via the BlockSpec index maps (b -> b // H) rather
than materializing the repeat.  P=64, N=64 state tiles align with the
8x128 VPU lanes; the outer grid parallelizes B*H across cores.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba2_ssd_pallas"]


def _ssd_kernel(x_ref, b_ref, c_ref, dc_ref, dt_ref, s0_ref, y_ref, sT_ref,
                state_scr, *, chunk: int):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)  # (chunk, P)
    bm = b_ref[0].astype(jnp.float32)  # (chunk, N)
    cm = c_ref[0].astype(jnp.float32)  # (chunk, N)
    dc = dc_ref[0].astype(jnp.float32)  # (chunk,)
    dt = dt_ref[0].astype(jnp.float32)  # (chunk,)

    def step(t, carry):
        h, y = carry
        upd = dt[t] * (x[t][:, None] * bm[t][None, :])  # (P, N)
        h = dc[t] * h + upd
        yt = h @ cm[t]  # (P,)
        y = y.at[t].set(yt)
        return h, y

    y0 = jnp.zeros_like(x)
    h_final, y = jax.lax.fori_loop(0, chunk, step, (state_scr[...], y0))
    state_scr[...] = h_final
    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == nc - 1)
    def _emit():
        sT_ref[0] = state_scr[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_ssd_pallas(
    x: jax.Array,  # (B, S, H, P)
    Bmat: jax.Array,  # (B, S, N)
    Cmat: jax.Array,  # (B, S, N)
    decay: jax.Array,  # (B, S, H)
    dt: jax.Array,  # (B, S, H)
    state: Optional[jax.Array] = None,  # (B, H, P, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, S, H, P = x.shape
    N = Bmat.shape[-1]
    ch = min(chunk, S)
    if S % ch:
        raise ValueError(f"S={S} must be a multiple of chunk={ch}")
    s0 = (state if state is not None
          else jnp.zeros((B, H, P, N), jnp.float32)).astype(jnp.float32)

    # flatten (B, H) into the parallel grid dim; B/C index-map back to b
    xf = jnp.moveaxis(x, 2, 1).reshape(B * H, S, P)
    dcf = jnp.moveaxis(decay, 2, 1).reshape(B * H, S)
    dtf = jnp.moveaxis(dt, 2, 1).reshape(B * H, S)
    s0f = s0.reshape(B * H, P, N)

    t_map = lambda g, c: (g, c, 0)
    bc_map = lambda g, c: (g // H, c, 0)
    v_map = lambda g, c: (g, c)
    s_map = lambda g, c: (g, 0, 0)

    y, sT = pl.pallas_call(
        functools.partial(_ssd_kernel, chunk=ch),
        grid=(B * H, S // ch),
        in_specs=[
            pl.BlockSpec((1, ch, P), t_map),
            pl.BlockSpec((1, ch, N), bc_map),
            pl.BlockSpec((1, ch, N), bc_map),
            pl.BlockSpec((1, ch), v_map),
            pl.BlockSpec((1, ch), v_map),
            pl.BlockSpec((1, P, N), s_map),
        ],
        out_specs=[
            pl.BlockSpec((1, ch, P), t_map),
            pl.BlockSpec((1, P, N), s_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((B * H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xf, Bmat, Cmat, dcf, dtf, s0f)
    y = y.reshape(B, H, S, P)
    return jnp.moveaxis(y, 1, 2), sT.reshape(B, H, P, N)
