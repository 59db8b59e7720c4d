"""Operations and bytes that the algorithm needs, from the shapes alone.

What is counted is what the work needs, not what today's code moves: the
weights once per decode round, each live row's KV cache only up to its
position, the logits that are used, and no recomputation.  So a later
change that removes waste cannot push a share of the roofline over 100%.
``m`` is ``reference.dims(config)``; weights and caches are bfloat16 when
served.
"""
from __future__ import annotations

BF16 = 2


def matmul_params(m: dict) -> int:
    """Weights of one layer's matrix multiplications."""
    d, H, Hkv, hd, ff = m["d"], m["H"], m["Hkv"], m["hd"], m["ff"]
    return d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * ff


def layer_params(m: dict) -> int:
    """All weights of one layer: matrices, norms, biases."""
    extra = 2 * m["d"]
    if m["qk_norm"]:
        extra += 2 * m["hd"]
    if m["bias"]:
        extra += (m["H"] + 2 * m["Hkv"]) * m["hd"]
    return matmul_params(m) + extra


def n_params(m: dict) -> int:
    """Every weight: layers, final norm, embedding and untied head."""
    return m["L"] * layer_params(m) + m["d"] + 2 * m["V"] * m["d"]


def attn_flops(m: dict, keys_total: int) -> int:
    """Q K^T and P V of query rows that see ``keys_total`` keys between
    them (each query-key pair costs 4 * hd flops per head and layer)."""
    return 4 * m["L"] * m["H"] * m["hd"] * keys_total


def kv_bytes(m: dict) -> int:
    """Cache bytes of one position across all layers (K and V)."""
    return m["L"] * 2 * m["Hkv"] * m["hd"] * BF16


def weight_bytes(m: dict) -> int:
    """Weights one decode round or prefill reads: all but the embedding
    table, of which it reads only its rows."""
    return (m["L"] * layer_params(m) + m["d"] + m["d"] * m["V"]) * BF16


def token_flops(m: dict) -> int:
    """Matrix multiplications of one token through every layer."""
    return 2 * m["L"] * matmul_params(m)


def decode(m: dict, rounds: int, positions: list) -> tuple:
    """(flops, bytes) of ``rounds`` decode rounds that made a token at each
    of ``positions`` (the index written; it attends ``pos + 1`` keys)."""
    keys = sum(p + 1 for p in positions)
    n = len(positions)
    flops = n * (token_flops(m) + 2 * m["d"] * m["V"]) + attn_flops(m, keys)
    bytes_ = (rounds * weight_bytes(m) + kv_bytes(m) * (keys + n)
              + n * m["d"] * BF16)
    return flops, bytes_


def prefill(m: dict, prompt: int) -> tuple:
    """(flops, bytes) of one batch-1 prefill; only the last position's
    logits are used."""
    keys = prompt * (prompt + 1) // 2
    flops = (prompt * token_flops(m) + 2 * m["d"] * m["V"]
             + attn_flops(m, keys))
    bytes_ = (weight_bytes(m) + kv_bytes(m) * prompt
              + prompt * m["d"] * BF16)
    return flops, bytes_



def train(m: dict, seqs: int, seq_len: int) -> int:
    """Flops of training steps over ``seqs`` sequences of ``seq_len``
    tokens: 6 per matmul weight and token (forward, and the backward's two
    products) over every layer's matrices and the unembedding (the
    embedding is a lookup), plus causal attention three times over (each
    query sees the keys up to its own).  Recomputation is not counted."""
    tokens = seqs * seq_len
    weights = m["L"] * matmul_params(m) + m["d"] * m["V"]
    keys = seqs * seq_len * (seq_len + 1) // 2
    return 6 * weights * tokens + 3 * attn_flops(m, keys)
