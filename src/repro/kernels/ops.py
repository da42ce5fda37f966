"""Jit'd dispatch wrappers for the Pallas kernels.

Backend selection:
  * ``ref``     — pure-jnp oracles (the default; fully differentiable)
  * ``pallas``  — pl.pallas_call kernels, compiled for the TPU.  The entry
                  points select it when they find a TPU
                  (``repro.launch.device.select_kernel_backend``).  Kernels
                  run in interpret mode only when a caller asks for it
                  (``set_backend("pallas", interpret=True)``, as the CPU
                  tests do).

Single-query decode attention (a ``kv_mask`` over the padded cache) has no
kernel: :func:`flash_attention` runs it on the reference path under either
backend (``DECODE_ATTENTION_PATH`` names it for the server's report).

Kernel forwards are wrapped in ``jax.custom_vjp`` with the ref backward, so
the pallas backend remains trainable without hand-written backward kernels
(the recompute matches the remat policy anyway).
"""
from __future__ import annotations

import contextlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_pallas
from .mamba2_scan import mamba2_ssd_pallas
from .rmsnorm import rmsnorm_pallas
from .rwkv6_scan import rwkv6_scan_pallas
from .swiglu import swiglu_pallas

__all__ = [
    "set_backend",
    "backend_scope",
    "get_backend",
    "rmsnorm",
    "swiglu",
    "flash_attention",
    "rwkv6_scan",
    "mamba2_scan",
    "DECODE_ATTENTION_PATH",
]

_BACKEND = "ref"
_INTERPRET = False
#: what decode attention (``kv_mask`` set) runs on, whatever the backend
DECODE_ATTENTION_PATH = "ref (masked jnp attention; no decode kernel)"
#: key-length threshold above which the ref backend switches to the chunked
#: online-softmax attention (never materializes the S x T logits)
FLASH_CHUNK_THRESHOLD = 4096
FLASH_CHUNK = 1024


def set_backend(name: str, *, interpret: Optional[bool] = None) -> None:
    global _BACKEND, _INTERPRET
    if name not in ("ref", "pallas"):
        raise ValueError(name)
    _BACKEND = name
    if interpret is not None:
        _INTERPRET = interpret


def get_backend() -> str:
    return _BACKEND


@contextlib.contextmanager
def backend_scope(name: str, *, interpret: Optional[bool] = None):
    prev = (_BACKEND, _INTERPRET)
    set_backend(name, interpret=interpret)
    try:
        yield
    finally:
        set_backend(prev[0], interpret=prev[1])


def _ref_vjp(pallas_fn, ref_fn):
    """Kernel forward + oracle backward."""

    @jax.custom_vjp
    def f(*args):
        return pallas_fn(*args)

    def fwd(*args):
        return pallas_fn(*args), args

    def bwd(args, g):
        _, vjp = jax.vjp(lambda *a: ref_fn(*a), *args)
        return vjp(g)

    f.defvjp(fwd, bwd)
    return f


# --------------------------------------------------------------------------
def rmsnorm(x: jax.Array, scale: jax.Array, *, eps: float = 1e-5) -> jax.Array:
    if _BACKEND == "ref":
        return ref.rmsnorm(x, scale, eps)
    fn = _ref_vjp(
        lambda a, s: rmsnorm_pallas(a, s, eps=eps, interpret=_INTERPRET),
        lambda a, s: ref.rmsnorm(a, s, eps),
    )
    return fn(x, scale)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    if _BACKEND == "ref":
        return ref.swiglu(gate, up)
    fn = _ref_vjp(
        lambda g, u: swiglu_pallas(g, u, interpret=_INTERPRET),
        ref.swiglu,
    )
    return fn(gate, up)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    kv_mask: Optional[jax.Array] = None,
) -> jax.Array:
    if _BACKEND == "ref" or kv_mask is not None:
        # no kernel takes an arbitrary kv mask: decode runs here under both
        # backends (DECODE_ATTENTION_PATH)
        if k.shape[2] > FLASH_CHUNK_THRESHOLD and q.shape[2] > 1:
            # chunked online softmax for long prefill/train; single-query
            # decode keeps the direct masked path (scan overhead loses there)
            return ref.flash_attention_chunked(
                q, k, v, causal=causal, scale=scale, kv_mask=kv_mask,
                chunk=FLASH_CHUNK,
            )
        return ref.flash_attention(q, k, v, causal=causal, scale=scale, kv_mask=kv_mask)
    fn = _ref_vjp(
        lambda a, b, c: flash_attention_pallas(
            a, b, c, causal=causal, scale=scale, interpret=_INTERPRET
        ),
        lambda a, b, c: ref.flash_attention(a, b, c, causal=causal, scale=scale),
    )
    return fn(q, k, v)


def rwkv6_scan(
    r: jax.Array,
    k: jax.Array,
    v: jax.Array,
    w: jax.Array,
    u: jax.Array,
    state: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    if _BACKEND == "ref":
        return ref.rwkv6_scan(r, k, v, w, u, state)
    B, H, S, hd = r.shape
    chunk = min(S, 128)
    pad = -S % chunk
    if pad:
        # padded steps carry k = v = 0 and decay w = 1: they leave the state
        # as it was, and their outputs are cut off below
        def tail(a, value):
            return jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)),
                           constant_values=value)

        r, k, v, w = tail(r, 0), tail(k, 0), tail(v, 0), tail(w, 1)
    s0 = state if state is not None else jnp.zeros((B, H, hd, hd), jnp.float32)
    fn = _ref_vjp(
        lambda *a: rwkv6_scan_pallas(*a, chunk=chunk, interpret=_INTERPRET),
        lambda *a: ref.rwkv6_scan(*a),
    )
    y, s_out = fn(r, k, v, w, u, s0)
    return y[:, :, :S], s_out


def mamba2_scan(
    x: jax.Array,  # (B, S, H, P)
    Bmat: jax.Array,  # (B, S, N)
    Cmat: jax.Array,  # (B, S, N)
    dA: jax.Array,  # (B, S, H) log decay, dt * A
    dt: jax.Array,  # (B, S, H)
    state: Optional[jax.Array] = None,  # (B, H, P, N)
    *,
    chunk: int = 256,
) -> Tuple[jax.Array, jax.Array]:
    """The Mamba2 SSD scan: (y (B,S,H,P) f32, final state (B,H,P,N) f32).
    ``chunk`` is the kernel's chunk of steps, and on the ref backend the
    chunk of the recompute in the backward pass."""
    if _BACKEND == "ref":
        return ref.mamba2_ssd_scan(x, Bmat, Cmat, jnp.exp(dA), dt, state,
                                   remat_chunk=chunk)
    B, S, H, P = x.shape
    N = Bmat.shape[-1]
    q = min(S, chunk)
    pad = -S % q
    if pad:
        # padded steps carry x = B = C = 0, dt = 0 and log decay 0: they
        # leave the state as it was, and their outputs are cut off below
        x, Bmat, Cmat, dA, dt = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, Bmat, Cmat, dA, dt))
    s0 = state if state is not None else jnp.zeros((B, H, P, N), jnp.float32)
    fn = _ref_vjp(
        lambda *a: mamba2_ssd_pallas(*a, chunk=q, interpret=_INTERPRET),
        lambda x, b, c, a, d, s: ref.mamba2_ssd_scan(x, b, c, jnp.exp(a), d,
                                                     s),
    )
    y, s_out = fn(x, Bmat, Cmat, dA, dt, s0)
    return y[:, :S], s_out
