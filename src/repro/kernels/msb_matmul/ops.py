"""Dispatch layer: packed-weight matmul for models and serving.

``packed_matmul(x, pq)`` is the hot-path entry point: its input is a
pre-packed ``PackedQTensor`` (produced once at load time by
``core.policy.pack_params``), so nothing is re-laid-out per call. On TPU it
runs the fused Pallas kernel; elsewhere it mirrors simulation-mode math
exactly (dequantize, then the same einsum ``dense()`` uses) so packed and
simulated execution are token-identical off-TPU by construction.

``qtensor_matmul`` / ``to_kernel_layout`` remain as test/bench conveniences
over the raw ``QTensor``; the packing they do is memoized on the concrete
codes/scales buffers, fixing the old per-invocation ``to_kernel_layout``
(int4 pack + scale reshape on every call).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.quantize import PackedQTensor, QTensor, pack_qtensor
from .msb_matmul import BLOCK, msb_matmul
from .ref import msb_matmul_ref


def _on_tpu():
    return jax.default_backend() == "tpu"


def packed_matmul(x, pq: PackedQTensor, bias=None, *, use_kernel=None,
                  interpret=None, psum_axis=None):
    """y = x @ dequant(pq) (+ bias). x: (..., K); returns (..., pq.n).

    ``pq`` must be 2-D storage (a scan-sliced or per-expert leaf). The
    kernel path fuses the bias add; the jnp path replays simulation-mode
    math on the dequantized weights.

    Tensor parallelism (DESIGN.md Sec. 10): under ``shard_map`` the local
    shard of an N- (column-) sharded weight needs nothing special — pass
    the local ``pq`` (after ``core.policy.tp_localize``) and the output is
    the local slice of features. For a K- (row-) sharded weight the local
    matmul yields *partial products*; pass ``psum_axis`` (the mesh axis
    name) and the dispatch psums them — the bias, if any, is then added
    once *after* the psum rather than fused per rank.
    """
    if pq.packed.ndim != 2:
        raise ValueError(f"packed_matmul wants 2-D storage, got "
                         f"{pq.packed.shape}; slice stacked params first")
    if use_kernel is None:
        use_kernel = _on_tpu()
    fused_bias = bias if psum_axis is None else None
    if use_kernel:
        if interpret is None:
            interpret = not _on_tpu()
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        y = msb_matmul(x2, pq.packed, pq.scales, fused_bias,
                       kblocked=pq.kblocked, interpret=interpret)
        y = y[:, : pq.n].reshape(*lead, pq.n).astype(x.dtype)
    else:
        w = pq.dequantize()                  # (K, n), exact simulation math
        y = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
        if fused_bias is not None:
            y = y + fused_bias.astype(y.dtype)
    if psum_axis is not None:
        y = jax.lax.psum(y, psum_axis)
        if bias is not None:
            y = y + bias.astype(y.dtype)
    return y


# -- QTensor conveniences (tests / benchmarks) -------------------------------

def to_kernel_layout(q: QTensor):
    """2-D QTensor (codes (K, N)) -> kernel (packed, n-blocked scales)."""
    assert q.bits == 4 and q.block == BLOCK, "kernel supports 4-bit block-64"
    pq = pack_qtensor(q)
    return pq.packed, pq.scales


_PACK_CACHE = {}


def _cached_pack(q: QTensor) -> PackedQTensor:
    """Memoize packing on the concrete buffers so repeated calls don't
    re-run the layout pass. Tracers (inside jit) are never cached — models
    should carry PackedQTensor params instead of packing under trace."""
    if isinstance(q.codes, jax.core.Tracer):
        return pack_qtensor(q)
    key = (id(q.codes), id(q.scales))
    hit = _PACK_CACHE.get(key)
    # retain BOTH buffers and identity-check both: an id() can be recycled
    # after gc, and a stale hit would silently pack the wrong scales
    if hit is None or hit[0] is not q.codes or hit[1] is not q.scales:
        if len(_PACK_CACHE) > 256:
            _PACK_CACHE.clear()
        hit = (q.codes, q.scales, pack_qtensor(q))
        _PACK_CACHE[key] = hit
    return hit[2]


def qtensor_matmul(x, q: QTensor, *, use_kernel=None, interpret=None):
    """y = x @ dequant(q). x: (..., K). Packs on first use (memoized)."""
    return packed_matmul(x, _cached_pack(q), use_kernel=use_kernel,
                         interpret=interpret)
