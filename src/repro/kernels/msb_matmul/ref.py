"""Pure-jnp oracle for the msb_matmul kernel.

The nibble decode and per-block scale gather are the *same* code the
packed execution path runs off-TPU (core.quantize), so the oracle cannot
drift from the storage format — only the in-kernel 8-way-select variant
(msb_matmul._dequant_tile) is a separate implementation, and it is what
these functions validate.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core.quantize import (PACK_BLOCK as BLOCK, PACK_LEVELS as LEVELS,
                              PackedQTensor, _unpack_nibbles,
                              packed_dequantize)


def unpack_ref(packed, n):
    """uint8 (K, N//2) -> (level (K,N) int32, sign (K,N) f32)."""
    level, sign = _unpack_nibbles(packed)
    return level[:, :n], sign[:, :n]


def dequant_ref(packed, scales, kblocked=False):
    """Dequantize to (K, N) f32.

    scales: (N//64, 8, K) n-blocked, or (K//64, 8, N) k-blocked."""
    n = packed.shape[-1] * 2
    pq = PackedQTensor(packed, scales, 4, BLOCK, jnp.float32, n,
                       kblocked=kblocked)
    return packed_dequantize(pq)


def msb_matmul_ref(x, packed, scales, bias=None, kblocked=False):
    w = dequant_ref(packed, scales, kblocked=kblocked).astype(x.dtype)
    y = jnp.dot(x, w, preferred_element_type=jnp.float32)
    if bias is not None:
        y = y + bias.reshape(1, -1).astype(y.dtype)
    return y.astype(x.dtype)
