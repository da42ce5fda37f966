"""Operations and bytes that each counted piece of work needs, from shapes.

``m`` is a configuration's ``model`` table (``configs/<name>.json``): a
decoder with grouped-query attention, a SwiGLU feed-forward and embeddings
tied to the output head.  Counts are of the work the algorithm needs, not
of what a given program happens to do: a program that does more (padded
lanes, a whole padded cache, recomputation) reads as a smaller share.
"""
from __future__ import annotations

from typing import Dict, Iterable

__all__ = ["layer_params", "weight_params", "weight_bytes", "kv_bytes",
           "prefill_flops", "decode_token_flops", "decode_step_bytes",
           "flash_attention_cost", "sync_recv_bytes", "tree_bytes"]

DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _width(m: Dict) -> int:
    return DTYPE_BYTES[m["dtype"]]


def layer_params(m: Dict) -> int:
    """Matrix and norm parameters of one decoder layer."""
    d, q = m["d_model"], m["num_heads"] * m["head_dim"]
    kv = m["num_kv_heads"] * m["head_dim"]
    return d * q + 2 * d * kv + q * d + 3 * d * m["d_ff"] + 2 * d


def weight_params(m: Dict) -> int:
    """Every parameter as the program holds it: padded vocabulary rows of
    the tied embedding, the layers and the final norm."""
    return (m["padded_vocab"] * m["d_model"] + m["num_layers"] * layer_params(m)
            + m["d_model"])


def weight_bytes(m: Dict) -> int:
    return weight_params(m) * _width(m)


def kv_bytes(m: Dict, tokens: int) -> int:
    """K and V of ``tokens`` positions of one sequence, every layer."""
    return (2 * m["num_layers"] * m["num_kv_heads"] * m["head_dim"] * tokens
            * _width(m))


def _matmul_flops_per_token(m: Dict) -> int:
    d, q = m["d_model"], m["num_heads"] * m["head_dim"]
    kv = m["num_kv_heads"] * m["head_dim"]
    return 2 * m["num_layers"] * (d * q + 2 * d * kv + q * d + 3 * d * m["d_ff"])


def _attn_flops(m: Dict, keys_attended: int) -> int:
    """Scores and the weighted sum of values: 4 * head_dim operations per
    query head and key attended, summed over the queries."""
    return 4 * m["num_layers"] * m["num_heads"] * m["head_dim"] * keys_attended


def _head_flops(m: Dict, tokens: int) -> int:
    return 2 * m["d_model"] * m["vocab_size"] * tokens


def prefill_flops(m: Dict, prompt_len: int) -> int:
    """One prompt of one sequence: every layer over every prompt token,
    causal attention (query i attends i + 1 keys), and the head at the last
    position, which is all that the first token needs."""
    s = prompt_len
    return (_matmul_flops_per_token(m) * s + _attn_flops(m, s * (s + 1) // 2)
            + _head_flops(m, 1))


def decode_token_flops(m: Dict, position: int) -> int:
    """One decoded token at ``position`` (0-based), attending position + 1
    keys, with the head."""
    return (_matmul_flops_per_token(m) + _attn_flops(m, position + 1)
            + _head_flops(m, 1))


def decode_step_bytes(m: Dict, positions: Iterable[int]) -> int:
    """The least bytes one decode step over these sequences moves: every
    weight once, each sequence's K and V up to its position, and the new
    K and V it writes."""
    pos = list(positions)
    if not pos:
        return 0
    return (weight_bytes(m) + sum(kv_bytes(m, p + 1) for p in pos)
            + len(pos) * kv_bytes(m, 1))


def flash_attention_cost(m: Dict, lanes: int, seq: int) -> Dict[str, int]:
    """Causal self-attention over ``seq`` tokens in ``lanes`` sequences, one
    layer: operations on the causal triangle, and the bytes of Q, K, V and
    the output in the model's dtype."""
    h, hkv, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    flops = 4 * lanes * h * hd * (seq * (seq + 1) // 2)
    nbytes = lanes * seq * hd * (2 * h + 2 * hkv) * _width(m)
    return {"flops": flops, "bytes": nbytes}


def tree_bytes(leaf_shapes: Iterable, width: int) -> int:
    total = 0
    for shape in leaf_shapes:
        n = 1
        for s in shape:
            n *= int(s)
        total += n * width
    return total


def sync_recv_bytes(tree_nbytes: int, chips: int) -> int:
    """Bytes each chip must receive for a reduce-scatter and then an
    all-gather of a tree of ``tree_nbytes`` over ``chips`` data-parallel
    chips: (chips - 1) / chips of the tree for each."""
    return 2 * tree_nbytes * (chips - 1) // chips
