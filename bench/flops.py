"""Model operations per token, from a configuration's published numbers.

A token at position p (0-based) of its sequence attends to p + 1 positions.
Matrix products count 2 operations per multiply-add: the seven projections
of each layer, the attention scores and the weighted sum at the live
context, and the unembedding over the real vocabulary. Norms, rotary
embeddings and softmax are left out (under 1%).
"""
from __future__ import annotations

from typing import Iterable


def matmul_flops_per_token(arch: dict) -> float:
    d = arch["hidden_size"]
    h = arch["num_attention_heads"]
    kv = arch["num_key_value_heads"]
    hd = arch.get("head_dim") or d // h
    ff = arch["intermediate_size"]
    per_layer = 2 * (d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff)
    return arch["num_hidden_layers"] * per_layer + 2 * d * arch["vocab_size"]


def attention_flops(arch: dict, context: int) -> float:
    """Scores and weighted sum for one token attending to ``context``."""
    d = arch["hidden_size"]
    h = arch["num_attention_heads"]
    hd = arch.get("head_dim") or d // h
    return arch["num_hidden_layers"] * 4.0 * h * hd * context


def token_flops(arch: dict, positions: Iterable[int]) -> float:
    """Operations of the tokens at ``positions`` (0-based)."""
    mm = matmul_flops_per_token(arch)
    total = 0.0
    for p in positions:
        total += mm + attention_flops(arch, p + 1)
    return total


def span_flops(arch: dict, start: int, stop: int) -> float:
    """Operations of the tokens at positions start..stop-1 of a sequence,
    in closed form."""
    n = stop - start
    if n <= 0:
        return 0.0
    ctx_sum = (start + 1 + stop) * n / 2.0          # sum of (p + 1)
    return (n * matmul_flops_per_token(arch)
            + attention_flops(arch, 1) * ctx_sum)
