"""Pallas-TPU Mamba2 SSD scan, in the chunked (matrix) form of the SSD paper.

Per head (P = head_dim, N = state_dim), with ``a_t = dt_t * A`` the log
decay of step t:

    h_t = exp(a_t) h_{t-1} + dt_t x_t B_t^T        h: (P, N)
    y_t = h_t C_t

Grid: (B*H, S/Q) — chunks of Q steps are the sequential axis; the (P x N)
f32 state lives in VMEM scratch and persists across chunks.  Inside a chunk
nothing is sequential: with ``l`` the cumulative log decay from the chunk's
start,

    y_i   = sum_{j<=i} (C_i . B_j) exp(l_i - l_j) dt_j x_j  +  exp(l_i) h C_i
    h_new = exp(l_Q) h + sum_j exp(l_Q - l_j) dt_j x_j B_j^T

which is four matrix products on the MXU.  The kernel works on x and y
transposed, (P, Q) tiles, so that every product is ``a @ b`` or
``a @ b.T``; B/C are shared by all heads of a sequence (one group), read
through the index map (g -> g // H) rather than repeated.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba2_ssd_pallas"]

HIGHEST = jax.lax.Precision.HIGHEST


def _ssd_kernel(x_ref, b_ref, c_ref, row_ref, col_ref, s0_ref, y_ref, sT_ref,
                state_scr):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = s0_ref[0].astype(jnp.float32)

    f32 = jnp.float32
    xT = x_ref[0].astype(f32)  # (P, Q)
    bm = b_ref[0].astype(f32)  # (Q, N)
    cm = c_ref[0].astype(f32)  # (Q, N)
    rows = row_ref[0]  # (2, Q): cumulative log decay, dt
    cols = col_ref[0]  # (Q, 2): the same as columns
    l_row, dt_row = rows[0:1, :], rows[1:2, :]
    l_col, dt_col = cols[:, 0:1], cols[:, 1:2]
    q = xT.shape[1]
    j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)  # source step
    i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)  # output step

    # (M^T)[j, i] = (B_j . C_i) exp(l_i - l_j) dt_j, causal (j <= i)
    seg = jnp.where(j <= i, l_row - l_col, -jnp.inf)
    mT = (jnp.dot(bm, cm.T, precision=HIGHEST, preferred_element_type=f32)
          * jnp.exp(seg) * dt_col)
    h = state_scr[...]  # (P, N)
    yT = jnp.dot(xT, mT, precision=HIGHEST, preferred_element_type=f32)
    yT += jnp.dot(h, cm.T, precision=HIGHEST,
                  preferred_element_type=f32) * jnp.exp(l_row)

    last = jnp.sum(jnp.where(i[0:1, :] == q - 1, l_row, 0.0), axis=1,
                   keepdims=True)  # (1, 1): log decay of the whole chunk
    w_row = dt_row * jnp.exp(last - l_row)
    h = jnp.exp(last) * h + jnp.dot(xT * w_row, bm, precision=HIGHEST,
                                    preferred_element_type=f32)
    state_scr[...] = h
    y_ref[0] = yT.astype(y_ref.dtype)

    @pl.when(ci == pl.num_programs(1) - 1)
    def _emit():
        sT_ref[0] = h


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba2_ssd_pallas(
    x: jax.Array,  # (B, S, H, P)
    Bmat: jax.Array,  # (B, S, N)
    Cmat: jax.Array,  # (B, S, N)
    dA: jax.Array,  # (B, S, H) log decay, dt * A
    dt: jax.Array,  # (B, S, H)
    state: Optional[jax.Array] = None,  # (B, H, P, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y: (B, S, H, P) f32, final state: (B, H, P, N) f32)."""
    B, S, H, P = x.shape
    N = Bmat.shape[-1]
    q = min(chunk, S)
    if S % q:
        raise ValueError(f"S={S} must be a multiple of chunk={q}")
    s0 = (state if state is not None
          else jnp.zeros((B, H, P, N), jnp.float32)).astype(jnp.float32)

    # (B, H) flattened into the parallel grid dim; x and y as (P, S) tiles
    xT = jnp.transpose(x, (0, 2, 3, 1)).reshape(B * H, P, S)
    cum = jnp.cumsum(dA.astype(jnp.float32).reshape(B, S // q, q, H), axis=2)
    per_head = lambda a: jnp.moveaxis(a.reshape(B, S, H), 2, 1).reshape(
        B * H, S)
    scal = jnp.stack([per_head(cum), per_head(dt.astype(jnp.float32))], 1)
    s0f = s0.reshape(B * H, P, N)

    y, sT = pl.pallas_call(
        _ssd_kernel,
        grid=(B * H, S // q),
        in_specs=[
            pl.BlockSpec((1, P, q), lambda g, c: (g, 0, c)),
            pl.BlockSpec((1, q, N), lambda g, c: (g // H, c, 0)),
            pl.BlockSpec((1, q, N), lambda g, c: (g // H, c, 0)),
            pl.BlockSpec((1, 2, q), lambda g, c: (g, 0, c)),
            pl.BlockSpec((1, q, 2), lambda g, c: (g, c, 0)),
            pl.BlockSpec((1, P, N), lambda g, c: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, P, q), lambda g, c: (g, 0, c)),
            pl.BlockSpec((1, P, N), lambda g, c: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, P, S), jnp.float32),
            jax.ShapeDtypeStruct((B * H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="mamba2_scan",
    )(xT, Bmat, Cmat, scal, jnp.swapaxes(scal, 1, 2), s0f)
    y = jnp.transpose(y.reshape(B, H, P, S), (0, 3, 1, 2))
    return y, sT.reshape(B, H, P, N)
