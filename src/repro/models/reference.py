"""Plain float32 reference of the granite-4.0-h hybrid (``layer_types``
models: Mamba2 and NoPE attention layers, each with its own SwiGLU MLP),
for the tests that hold the program to it.

Straightforward ``jax.numpy`` on one sequence, every matrix product under
``jax.default_matmul_precision("highest")``: no kernels, cache or batching,
the SSD a sequential scan over time.  It reads the program's parameter
layout (``models.model.init_params``) and imports nothing else of the
program's model code.  The equations, those of HF ``GraniteMoeHybrid``
with no experts (``r`` the residual multiplier):

    x = embed[tokens] * embedding_multiplier
    per layer:  x += r * mixer(rmsnorm(x) * ln1)
                x += r * ((silu(h Wg) * (h Wu)) Wd),  h = rmsnorm(x) * ln2
    mamba:      z | xBC | dt = h W_in;  xBC = silu(causal_conv(xBC) + b)
                dt = softplus(dt + dt_bias),  A = -exp(A_log)
                h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,  y_t = h_t C_t + D x_t
                y = rmsnorm(y * silu(z)) * w  (over the inner width)  W_out
    attention:  softmax(q k^T * attention_multiplier, causal) v  Wo,
                GQA, no positional encoding
    logits = rmsnorm(x) * final_norm  embed^T / logits_scaling

Departures from the published model, none of them in the mathematics: the
SwiGLU's gate and up projections are two matrices (HF holds them as one,
``input_linear``, split in half), and the vocabulary rows are padded with
zeros, which the logits leave out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig

__all__ = ["hybrid_logits"]


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _mamba(cfg: ModelConfig, w, h):
    S = h.shape[0]
    d_in = cfg.ssm.expand * cfg.d_model
    P, N, K = cfg.ssm.head_dim, cfg.ssm.state_dim, cfg.ssm.conv_dim
    H = d_in // P
    zxbcdt = h @ w["in_proj"]["w"]
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * N], axis=-1)
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = sum(padded[i:i + S] * w["conv_w"][i] for i in range(K))
    xbc = jax.nn.silu(conv + w["conv_b"])
    x, b, c = jnp.split(xbc, [d_in, d_in + N], axis=-1)
    x = x.reshape(S, H, P)
    dt = jax.nn.softplus(dt + w["dt_bias"])  # (S, H)
    a = -jnp.exp(w["a_log"])

    def step(state, t):
        xt, bt, ct, dtt = t
        state = (jnp.exp(dtt * a)[:, None, None] * state
                 + dtt[:, None, None] * xt[:, :, None] * bt[None, None, :])
        return state, state @ ct

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (x, b, c, dt))
    y = (y + w["d_skip"][None, :, None] * x).reshape(S, d_in)
    y = _rms(y * jax.nn.silu(z), w["norm_scale"], cfg.norm_eps)
    return y @ w["out_proj"]["w"]


def _attention(cfg: ModelConfig, w, h):
    S = h.shape[0]
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (h @ w["wq"]["w"]).reshape(S, H, hd)
    k = jnp.repeat((h @ w["wk"]["w"]).reshape(S, Hkv, hd), H // Hkv, axis=1)
    v = jnp.repeat((h @ w["wv"]["w"]).reshape(S, Hkv, hd), H // Hkv, axis=1)
    scale = cfg.attention_multiplier or hd ** -0.5
    scores = jnp.einsum("shd,thd->hst", q, k) * scale
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hst,thd->shd", probs, v).reshape(S, H * hd) @ w["wo"]["w"]


def hybrid_logits(cfg: ModelConfig, params, tokens) -> jax.Array:
    """(S, vocab) float32 logits of one sequence ``tokens`` (S,)."""
    if cfg.position_embedding != "nope" or not cfg.layer_types:
        raise ValueError(f"{cfg.name}: the reference is of NoPE layer_types "
                         f"models")
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    eps, r = cfg.norm_eps, cfg.residual_multiplier
    seen = {"mamba": 0, "attention": 0}
    with jax.default_matmul_precision("highest"):
        x = p["embed"][jnp.asarray(tokens)] * cfg.embedding_multiplier
        for i, kind in enumerate(cfg.layer_types):
            lw = jax.tree.map(lambda a: a[i], {k: p["layers"][k]
                                               for k in ("ln1", "ln2", "ffn")})
            k = seen[kind]
            seen[kind] += 1
            h = _rms(x, lw["ln1"]["scale"], eps)
            if kind == "mamba":
                out = _mamba(cfg, jax.tree.map(lambda a: a[k],
                                               p["layers"]["mamba"]), h)
            else:
                out = _attention(cfg, jax.tree.map(lambda a: a[k],
                                                   p["layers"]["attn"]), h)
            x = x + r * out
            f = lw["ffn"]
            h = _rms(x, lw["ln2"]["scale"], eps)
            x = x + r * ((jax.nn.silu(h @ f["gate"]["w"]) * (h @ f["up"]["w"]))
                         @ f["down"]["w"])
        x = _rms(x, p["final_norm"]["scale"], eps)
        logits = x @ p["embed"].T / cfg.logits_scaling
    return logits[:, :cfg.vocab_size]
