"""Operations and bytes of the Mamba2 / attention hybrid (granite-4.0-h),
from shapes.

``m`` is a configuration's ``model`` table (``configs/<name>.json``): the
layers of ``layer_types``, Mamba2 mixers (``mamba_*`` sizes, one group of
B/C) and GQA attention mixers without positional encoding, every layer with
its own SwiGLU MLP, embeddings tied to the output head.  As in ``flops.py``
the counts are of the work the algorithm needs, not of what a program
happens to do.  ``decode_token_flops`` and ``decode_step_bytes`` take the
arguments of their namesakes in ``flops.py``, so a serving driver counts
with either module.
"""
from __future__ import annotations

from typing import Dict, Iterable

__all__ = ["mamba_layers", "attention_layers", "weight_params",
           "weight_bytes", "state_bytes", "kv_bytes", "prefill_flops",
           "decode_token_flops", "decode_step_bytes", "mamba2_scan_cost"]

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
F32 = 4


def _width(m: Dict) -> int:
    return DTYPE_BYTES[m["dtype"]]


def _mamba_dims(m: Dict):
    """(d_in, P, H, N, conv channels, conv width)."""
    d_in = m["mamba_expand"] * m["d_model"]
    P, N = m["mamba_d_head"], m["mamba_d_state"]
    return d_in, P, d_in // P, N, d_in + 2 * N, m["mamba_d_conv"]


def mamba_layers(m: Dict) -> int:
    return list(m["layer_types"]).count("mamba")


def attention_layers(m: Dict) -> int:
    return list(m["layer_types"]).count("attention")


def _mamba_matrix_params(m: Dict) -> int:
    """in_proj (to z, x, B, C and dt) and out_proj of one Mamba mixer."""
    d = m["d_model"]
    d_in, _, H, N, _, _ = _mamba_dims(m)
    return d * (2 * d_in + 2 * N + H) + d_in * d


def _attn_matrix_params(m: Dict) -> int:
    d, q = m["d_model"], m["num_heads"] * m["head_dim"]
    return 2 * d * q + 2 * d * m["num_kv_heads"] * m["head_dim"]


def _mlp_params(m: Dict) -> int:
    return 3 * m["d_model"] * m["d_ff"]


def weight_params(m: Dict) -> int:
    """Every parameter as the program holds it: the tied embedding (padded
    rows too), per layer its MLP and two norms, per Mamba mixer its
    matrices, conv weight and bias, A_log, D, dt_bias and the gated norm's
    scale, per attention mixer its four matrices, and the final norm."""
    d = m["d_model"]
    d_in, _, H, _, ch, K = _mamba_dims(m)
    mamba = _mamba_matrix_params(m) + K * ch + ch + 3 * H + d_in
    return (m["padded_vocab"] * d
            + m["num_layers"] * (_mlp_params(m) + 2 * d)
            + mamba_layers(m) * mamba
            + attention_layers(m) * _attn_matrix_params(m) + d)


def weight_bytes(m: Dict) -> int:
    """As the program holds them: A_log, D and dt_bias in float32, every
    other parameter in the model's dtype."""
    H = _mamba_dims(m)[2]
    f32 = mamba_layers(m) * 3 * H
    return (weight_params(m) - f32) * _width(m) + f32 * F32


def state_bytes(m: Dict) -> int:
    """The recurrent state of one sequence, every Mamba layer: the SSM
    state (H, P, N) in float32 and the conv state (width - 1, channels) in
    the model's dtype."""
    _, P, H, N, ch, K = _mamba_dims(m)
    return mamba_layers(m) * (H * P * N * F32 + (K - 1) * ch * _width(m))


def kv_bytes(m: Dict, tokens: int) -> int:
    """K and V of ``tokens`` positions of one sequence, every attention
    layer."""
    return (2 * attention_layers(m) * m["num_kv_heads"] * m["head_dim"]
            * tokens * _width(m))


def _scan_ops_per_step(m: Dict) -> int:
    """One step of the SSD recurrence, every head: h = a h + (dt x) B^T
    (3 P N + P) and y = h C (2 P N)."""
    _, P, H, N, _, _ = _mamba_dims(m)
    return H * (5 * P * N + P)


def _per_token_flops(m: Dict) -> int:
    """Matrix products, the Mamba conv and scan, of one token through every
    layer (attention scores apart)."""
    ch, K = _mamba_dims(m)[4:]
    return (2 * m["num_layers"] * _mlp_params(m)
            + mamba_layers(m) * (2 * _mamba_matrix_params(m) + 2 * K * ch
                                 + _scan_ops_per_step(m))
            + 2 * attention_layers(m) * _attn_matrix_params(m))


def _attn_flops(m: Dict, keys_attended: int) -> int:
    return (4 * attention_layers(m) * m["num_heads"] * m["head_dim"]
            * keys_attended)


def _head_flops(m: Dict) -> int:
    return 2 * m["d_model"] * m["vocab_size"]


def prefill_flops(m: Dict, prompt_len: int) -> int:
    """One prompt of one sequence: every layer over every prompt token,
    causal attention in the attention layers, and the head at the last
    position."""
    s = prompt_len
    return (_per_token_flops(m) * s + _attn_flops(m, s * (s + 1) // 2)
            + _head_flops(m))


def decode_token_flops(m: Dict, position: int) -> int:
    """One decoded token at ``position`` (0-based), its attention layers
    attending position + 1 keys, with the head."""
    return (_per_token_flops(m) + _attn_flops(m, position + 1)
            + _head_flops(m))


def decode_step_bytes(m: Dict, positions: Iterable[int]) -> int:
    """The least bytes one decode step over these sequences moves: every
    weight once; per sequence its recurrent state read and written, the K
    and V of the attention layers up to its position read and the new row
    written."""
    pos = list(positions)
    if not pos:
        return 0
    return (weight_bytes(m) + len(pos) * 2 * state_bytes(m)
            + sum(kv_bytes(m, p + 1) for p in pos) + len(pos) * kv_bytes(m, 1))


def mamba2_scan_cost(m: Dict, lanes: int, seq: int) -> Dict[str, int]:
    """The SSD scan of one Mamba layer over ``seq`` steps of ``lanes``
    sequences: the recurrence's operations (``_scan_ops_per_step``), and
    the bytes of its inputs read once (x, B and C in the model's dtype, dt
    and the log decay in float32), its output y in float32, and the state
    read and written in float32."""
    _, P, H, N, _, _ = _mamba_dims(m)
    per_step = (H * P + 2 * N) * _width(m) + 2 * H * F32 + H * P * F32
    return {"flops": lanes * seq * _scan_ops_per_step(m),
            "bytes": lanes * (seq * per_step + 2 * H * P * N * F32)}
