"""Plain reference of the granite-4.0-h hybrid: Mamba2 and NoPE GQA
attention layers in a fixed pattern, each layer with its own SwiGLU MLP
(HF ``GraniteMoeHybrid`` with no experts).

Straightforward ``jax.numpy`` in float32 at ``Precision.HIGHEST``, one
sequence at a time, no cache, no kernels, no batching; the SSD is a
sequential scan over time.  It imports nothing of the program under test.
The equations (``r`` the residual multiplier):

    x = embed[tokens] * embedding_multiplier
    per layer:  h = rmsnorm(x) * ln1
                x += r * mamba(h)  or  r * attention(h)   (``layer_types``)
                h = rmsnorm(x) * ln2
                x += r * (silu(h Wg) * (h Wu)) Wd
    mamba:      z | xBC | dt = h W_in
                xBC = silu(causal depthwise conv(xBC, width 4) + bias)
                x | B | C = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
                h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T     (per head, P x N)
                y_t = h_t C_t + D x_t
                out = (rmsnorm(y * silu(z)) * w) W_out   (norm over the inner width)
    attention:  softmax(q k^T * attention_multiplier, causal) v  Wo, GQA, no
                positional encoding; computed in blocks of queries so that a
                long sequence's scores fit
    logits = (rmsnorm(x) * final_norm) embed^T / logits_scaling   (tied head)

Departures from the published model, none in the mathematics: the SwiGLU's
gate and up projections are two matrices (HF holds them as one).

``init_weights`` makes the weights from a seed, on the device, in one
jitted call and in the layout the program serves: the tied embedding, the
norms and MLPs of every layer stacked on a leading layer axis, the Mamba
mixers and the attention mixers each stacked on their own in layer order.
The same weights feed the program and this reference.

``quant="fp8"`` is the control: every matrix product takes its operands
rounded to float8 (e4m3) with one scale per row of activations and per
output column of weights, the precision step below the configuration's
bfloat16; the scan and the other elementwise work stay in float32.
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
#: queries per block of the attention scores
QUERY_BLOCK = 512


def init_weights(m: Dict, seed: int):
    """Random weights for ``m`` (a configuration's ``model`` table) from
    ``seed``, as the configuration's ``assumed`` says."""
    return _init(_static(m), jnp.uint32(seed % 2**32))


def _static(m: Dict):
    keys = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
            "d_ff", "vocab_size", "padded_vocab", "dtype", "mamba_d_head",
            "mamba_d_state", "mamba_expand", "mamba_d_conv",
            "embedding_multiplier")
    return tuple((k, m[k]) for k in keys) + (
        ("layer_types", tuple(m["layer_types"])),)


def _dims(m: Dict):
    d_in = m["mamba_expand"] * m["d_model"]
    N = m["mamba_d_state"]
    return d_in, m["mamba_d_head"], d_in // m["mamba_d_head"], N, d_in + 2 * N


@functools.partial(jax.jit, static_argnums=0)
def _init(ms, seed):
    m = dict(ms)
    dt = jnp.dtype(m["dtype"])
    L, d, hd, ff = m["num_layers"], m["d_model"], m["head_dim"], m["d_ff"]
    q, kv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    d_in, P, H, N, ch = _dims(m)
    K = m["mamba_d_conv"]
    kinds = m["layer_types"]
    k_embed, k_common, k_mamba, k_attn = jax.random.split(
        jax.random.key(seed), 4)
    # the residual multiplier, not the init, scales the branches down; the
    # embedding times its multiplier enters the residual stream at 0.02.
    # With a 0.02 embedding the head returns the input token at every
    # position: 12 times its own embedding outweighs all 40 layers
    out_std = 0.02
    embed_std = 0.02 / m["embedding_multiplier"]

    def normal(key, shape, std):
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dt)

    def common(key):
        ks = jax.random.split(key, 3)
        return {"ln1": {"scale": jnp.ones((d,), dt)},
                "ln2": {"scale": jnp.ones((d,), dt)},
                "ffn": {"gate": {"w": normal(ks[0], (d, ff), 0.02)},
                        "up": {"w": normal(ks[1], (d, ff), 0.02)},
                        "down": {"w": normal(ks[2], (ff, d), out_std)}}}

    def mamba(key):
        ks = jax.random.split(key, 6)
        step = jnp.exp(jax.random.uniform(ks[4], (H,), jnp.float32,
                                          jnp.log(1e-3), jnp.log(1e-1)))
        return {
            "in_proj": {"w": normal(ks[0], (d, 2 * d_in + 2 * N + H), 0.02)},
            "conv_w": jax.random.uniform(ks[1], (K, ch), jnp.float32,
                                         -0.5, 0.5).astype(dt),
            "conv_b": jax.random.uniform(ks[2], (ch,), jnp.float32,
                                         -0.5, 0.5).astype(dt),
            "a_log": jnp.log(jax.random.uniform(ks[3], (H,), jnp.float32,
                                                1.0, 16.0)),
            "d_skip": jnp.ones((H,), jnp.float32),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),  # softplus^-1
            "norm_scale": jnp.ones((d_in,), dt),
            "out_proj": {"w": normal(ks[5], (d_in, d), out_std)},
        }

    def attn(key):
        ks = jax.random.split(key, 4)
        return {"wq": {"w": normal(ks[0], (d, q), 0.02)},
                "wk": {"w": normal(ks[1], (d, kv), 0.02)},
                "wv": {"w": normal(ks[2], (d, kv), 0.02)},
                "wo": {"w": normal(ks[3], (q, d), out_std)}}

    # one layer at a time: the float32 draws of a layer are the only
    # temporaries, not those of all layers at once
    layers = jax.lax.map(common, jax.random.split(k_common, L))
    for name, kind, key, fn in (("mamba", "mamba", k_mamba, mamba),
                                ("attn", "attention", k_attn, attn)):
        n = kinds.count(kind)
        if n:
            layers[name] = jax.lax.map(fn, jax.random.split(key, n))
    rows = jnp.arange(m["padded_vocab"])[:, None] < m["vocab_size"]
    embed = normal(k_embed, (m["padded_vocab"], d), embed_std)
    return {"embed": jnp.where(rows, embed, 0).astype(dt), "layers": layers,
            "final_norm": {"scale": jnp.ones((d,), dt)}}


def fake_quant(x, axis):
    """Round ``x`` to float8 (e4m3: 3 mantissa bits) with one scale per
    slice along ``axis``.  ``reduce_precision`` and not a cast there and
    back: a compiler may drop a pair of converts as excess precision."""
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


def _mamba(m, w, h, quant):
    S = h.shape[0]
    d_in, P, H, N, ch = _dims(m)
    K = m["mamba_d_conv"]
    z, xbc, dt = jnp.split(_mm(h, w["in_proj"]["w"], quant),
                           [d_in, d_in + ch], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, ch)), xbc])
    conv = sum(padded[i:i + S] * w["conv_w"][i] for i in range(K))
    x, b, c = jnp.split(jax.nn.silu(conv + w["conv_b"]), [d_in, d_in + N], -1)
    x = x.reshape(S, H, P)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # (S, H)
    a = -jnp.exp(w["a_log"])

    def step(state, t):
        xt, bt, ct, dtt = t
        state = (jnp.exp(dtt * a)[:, None, None] * state
                 + dtt[:, None, None] * xt[:, :, None] * bt[None, None, :])
        return state, jnp.einsum("hpn,n->hp", state, ct, precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, b, c, dt))
    y = (y + w["d_skip"][None, :, None] * x).reshape(S, d_in)
    y = _rmsnorm(y * jax.nn.silu(z), w["norm_scale"], m["norm_eps"])
    return _mm(y, w["out_proj"]["w"], quant)


def _attention(m, w, h, quant):
    """Causal GQA attention over the sequence, no positional encoding; the
    scores one block of queries at a time."""
    S = h.shape[0]
    H, Hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    rep = H // Hkv
    q = _mm(h, w["wq"]["w"], quant).reshape(S, H, hd).transpose(1, 0, 2)
    k = jnp.repeat(_mm(h, w["wk"]["w"], quant).reshape(S, Hkv, hd), rep, 1)
    v = jnp.repeat(_mm(h, w["wv"]["w"], quant).reshape(S, Hkv, hd), rep, 1)
    kT = k.transpose(1, 2, 0)  # (H, hd, S)
    vh = v.transpose(1, 0, 2)  # (H, S, hd)
    blk = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, axis=1)
        scores = _mm(qi, kT, quant) * m["attention_multiplier"]
        rows = i * blk + jnp.arange(blk)[:, None]
        probs = jax.nn.softmax(
            jnp.where(jnp.arange(S)[None, :] <= rows, scores, -jnp.inf), -1)
        return _mm(probs, vh, quant)  # (H, blk, hd)

    out = jax.lax.map(block, jnp.arange(S // blk))  # (nb, H, blk, hd)
    out = out.transpose(0, 2, 1, 3).reshape(S, H * hd)
    return _mm(out, w["wo"]["w"], quant)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _logits(ms, w, tokens, quant):
    m = dict(ms)
    kinds = m["layer_types"]
    period = next(kinds[:p] for p in range(1, len(kinds) + 1)
                  if len(kinds) % p == 0 and kinds == kinds[:p] * (len(kinds) // p))
    per = {k: period.count(k) for k in ("mamba", "attention")}
    eps, r = m["norm_eps"], m["residual_multiplier"]
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    at = lambda tree, i: jax.tree.map(  # noqa: E731
        lambda a: f32(jax.lax.dynamic_index_in_dim(a, i, 0, False)), tree)
    x = f32(w["embed"][tokens]) * m["embedding_multiplier"]
    layers = w["layers"]

    def one_period(x, p):
        seen = {"mamba": 0, "attention": 0}
        for j, kind in enumerate(period):
            lw = at({k: layers[k] for k in ("ln1", "ln2", "ffn")},
                    p * len(period) + j)
            idx = p * per[kind] + seen[kind]
            seen[kind] += 1
            h = _rmsnorm(x, lw["ln1"]["scale"], eps)
            if kind == "mamba":
                out = _mamba(m, at(layers["mamba"], idx), h, quant)
            else:
                out = _attention(m, at(layers["attn"], idx), h, quant)
            x = x + r * out
            f = lw["ffn"]
            h = _rmsnorm(x, lw["ln2"]["scale"], eps)
            g = jax.nn.silu(_mm(h, f["gate"]["w"], quant)) * _mm(
                h, f["up"]["w"], quant)
            x = x + r * _mm(g, f["down"]["w"], quant)
        return x, None

    x, _ = jax.lax.scan(one_period, x, jnp.arange(len(kinds) // len(period)))
    x = _rmsnorm(x, f32(w["final_norm"]["scale"]), eps)
    out = _mm(x, f32(w["embed"]).T, quant) / m["logits_scaling"]
    return out[:, : m["vocab_size"]]


def logits(m: Dict, w, tokens, quant: Optional[str] = None):
    """(S, vocab) float32 logits of one sequence ``tokens`` (S,).  Causal:
    positions past the sequence's real end may hold padding."""
    if quant not in (None, "fp8"):
        raise ValueError(f"unknown quant {quant!r}")
    if m["position_embedding"] != "nope" or m["mamba_n_groups"] != 1:
        raise ValueError("the reference is of NoPE attention and one group "
                         "of Mamba2 B/C")
    ms = _static(m) + tuple((k, m[k]) for k in (
        "norm_eps", "embedding_multiplier", "attention_multiplier",
        "residual_multiplier", "logits_scaling"))
    return _logits(ms, w, jnp.asarray(tokens, jnp.int32), quant == "fp8")
