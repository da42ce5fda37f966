"""granite-4.0-h-micro [hybrid] — 36 Mamba2 + 4 NoPE GQA layers, each with
its own SwiGLU MLP.
[hf:ibm-granite/granite-4.0-h-micro config.json, model_type granitemoehybrid]

The period is 10 layers, attention at the sixth: ``[m m m m m A m m m m]``.
Mamba2: 64 heads of 64, d_state 128, one group, expand 2, conv 4, chunk 256.
Attention has no positional encoding; the Granite multipliers are those of
the published config.
"""
from .base import ModelConfig, SSMConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    head_dim=64,
    d_ff=8192,  # shared_intermediate_size
    vocab_size=100352,
    tie_embeddings=True,
    norm_eps=1e-5,
    rope_theta=1e4,  # published, unused: position_embedding_type nope
    layer_types=_PERIOD * 4,
    position_embedding="nope",
    embedding_multiplier=12.0,
    attention_multiplier=0.015625,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    ssm=SSMConfig(kind="mamba2", head_dim=64, state_dim=128, expand=2,
                  conv_dim=4, scan_chunk=256),
)
