"""Plain reference of a decoder with grouped-query attention (granite-3 style).

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, one
sequence at a time, no cache, no kernels, no batching.  It imports nothing
of the program under test.  The equations:

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * ln1
                q, k, v = h Wq, h Wk, h Wv          (heads of head_dim)
                q, k = rope(q), rope(k)             (half rotation, theta)
                x += softmax(q k^T / sqrt(head_dim), causal) v  Wo
                h = rmsnorm(x) * ln2
                x += (silu(h Wg) * (h Wu)) Wd
    logits = rmsnorm(x) * final_norm  embed^T       (tied head)

Departures from the published Granite 3.0 model, which the program shares:
no embedding, attention, residual or logits multipliers (Granite scales
the embeddings by 12, attention by 1/64 instead of 1/sqrt(64), the residual
branches by 0.22 and divides the logits by 8).

``init_weights`` makes the weights from a seed, on the device, in one
jitted call and in the layout the program serves: tied embedding with the
vocabulary padded by zero rows, and every layer's tensors stacked on a
leading layer axis.  The same weights feed the program and this reference.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8 (e4m3) with one scale per row of activations and per
output column of weights, the precision step below the configuration's
bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

__all__ = ["init_weights", "logits", "fake_quant"]

HIGHEST = jax.lax.Precision.HIGHEST
#: largest finite value of ``reduce_precision`` to 4 exponent and 3
#: mantissa bits (IEEE-style e4m3, which keeps its top exponent for inf)
E4M3_MAX = 240.0


def init_weights(m: Dict, seed: int):
    """Random weights for ``m`` (a configuration's ``model`` table) from
    ``seed``: normal(0, 0.02), output projections scaled by 1/sqrt(2L),
    norms one, padded vocabulary rows zero."""
    return _init(_static(m), jnp.uint32(seed % 2**32))


def _static(m: Dict):
    keys = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
            "d_ff", "vocab_size", "padded_vocab", "dtype")
    return tuple((k, m[k]) for k in keys)


@functools.partial(jax.jit, static_argnums=0)
def _init(ms, seed):
    m = dict(ms)
    dt = jnp.dtype(m["dtype"])
    L, d, hd = m["num_layers"], m["d_model"], m["head_dim"]
    q, kv, ff = m["num_heads"] * hd, m["num_kv_heads"] * hd, m["d_ff"]
    k_embed, k_layers = jax.random.split(jax.random.key(seed))
    out_std = 0.02 / (2 * L) ** 0.5

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def layer(key):
        ks = jax.random.split(key, 7)
        return {
            "ln1": {"scale": jnp.ones((d,), dt)},
            "attn": {"wq": {"w": normal(ks[0], (d, q), 0.02)},
                     "wk": {"w": normal(ks[1], (d, kv), 0.02)},
                     "wv": {"w": normal(ks[2], (d, kv), 0.02)},
                     "wo": {"w": normal(ks[3], (q, d), out_std)}},
            "ln2": {"scale": jnp.ones((d,), dt)},
            "ffn": {"gate": {"w": normal(ks[4], (d, ff), 0.02)},
                    "up": {"w": normal(ks[5], (d, ff), 0.02)},
                    "down": {"w": normal(ks[6], (ff, d), out_std)}},
        }

    rows = jnp.arange(m["padded_vocab"])[:, None] < m["vocab_size"]
    embed = normal(k_embed, (m["padded_vocab"], d), 0.02)
    return {
        "embed": jnp.where(rows, embed, 0).astype(dt),
        # one layer at a time: the float32 draws of a layer are the only
        # temporaries, not those of all layers at once
        "layers": jax.lax.map(layer, jax.random.split(k_layers, L)),
        "final_norm": {"scale": jnp.ones((d,), dt)},
    }


def fake_quant(x, axis):
    """Round ``x`` to float8 (e4m3: 3 mantissa bits) with one scale per
    slice along ``axis``.  ``reduce_precision`` and not a cast there and
    back: a compiler may drop a pair of converts as excess precision (the
    TPU's did), never this op."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / E4M3_MAX, 1.0)
    return jax.lax.reduce_precision(x / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def _mm(a, b, quant):
    """a (..., k) @ b (k, n) in float32; with ``quant`` the operands are
    rounded per row of ``a`` and per column of ``b`` first."""
    if quant:
        a, b = fake_quant(a, -1), fake_quant(b, -2)
    return jnp.matmul(a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (S, heads, hd): rotate the two halves of each head by position."""
    S, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, quant):
    """q (S, H, hd), k/v (S, Hkv, hd): causal softmax attention."""
    S, H, hd = q.shape
    rep = H // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    qh, kh, vh = (a.transpose(1, 0, 2) for a in (q, k, v))  # (H, S, hd)
    scores = _mm(qh, kh.transpose(0, 2, 1), quant) / hd ** 0.5
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return _mm(probs, vh, quant).transpose(1, 0, 2).reshape(S, H * hd)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(ms, w, tokens, quant):
    m = dict(ms)
    eps, theta = m["norm_eps"], m["rope_theta"]
    H, Hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    f32 = lambda a: a.astype(jnp.float32)
    x = f32(w["embed"][tokens])
    S = tokens.shape[0]

    def layer(x, lw):
        lw = jax.tree.map(f32, lw)
        a = lw["attn"]
        h = _rmsnorm(x, lw["ln1"]["scale"], eps)
        q = _rope(_mm(h, a["wq"]["w"], quant).reshape(S, H, hd), theta)
        k = _rope(_mm(h, a["wk"]["w"], quant).reshape(S, Hkv, hd), theta)
        v = _mm(h, a["wv"]["w"], quant).reshape(S, Hkv, hd)
        x = x + _mm(_attention(q, k, v, quant), a["wo"]["w"], quant)
        f = lw["ffn"]
        h = _rmsnorm(x, lw["ln2"]["scale"], eps)
        g = jax.nn.silu(_mm(h, f["gate"]["w"], quant)) * _mm(h, f["up"]["w"], quant)
        return x + _mm(g, f["down"]["w"], quant), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rmsnorm(x, f32(w["final_norm"]["scale"]), eps)
    out = _mm(x, f32(w["embed"]).T, quant)
    return out[:, : m["vocab_size"]]


def logits(m: Dict, w, tokens, quant: Optional[str] = None):
    """(S, vocab) float32 logits of one sequence ``tokens`` (S,).  Causal:
    positions past the sequence's real end may hold padding."""
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown quant {quant!r}")
    ms = _static(m) + (("norm_eps", m["norm_eps"]),
                       ("rope_theta", m["rope_theta"]))
    return _logits(ms, w, jnp.asarray(tokens, jnp.int32), quant == "fp8")
