"""MSB quantized-tensor representation and quantize/dequantize API.

Representation (paper Sec. 4.1): each weight is ``w_hat = alpha_z * s`` with
``s in {-1, 0, +1}`` (0 only for exact-zero weights — the paper's zero-loss
special group) and ``z`` indexing ``2^{b-1}`` per-group positive scales.

Stored form:
  codes : int8, same shape as w; code = sign * (level + 1), 0 for zeros
  scales: (n_blocks, 2^{b-1}) — one codebook row per block (block-wise) or a
          single row (per-tensor)

``QTensor`` is a pytree, so quantized params flow through jit / device_put /
sharding like any array. A packed int4 form (two codes per byte, paired
across the halves of each 64-element block) feeds the Pallas fused
dequant-matmul kernel (kernels/msb_matmul).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from . import grouping


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QTensor:
    """MSB-quantized tensor. Behaves as a pytree (codes/scales are leaves).

    ``shard`` is static tensor-parallel metadata set by
    ``core.policy.tp_partition_params`` (None for single-device tensors):
    ``"n"`` = output (column) dim sharded, ``"k"`` = reduction (row) dim
    sharded — the consumer must ``psum`` the partial products — ``"e"`` =
    expert dim sharded, ``"v"`` = vocab rows of an unembedding table
    sharded (logits need an ``all_gather``). See DESIGN.md Sec. 10.
    """
    codes: jax.Array          # int8, logical shape of w
    scales: jax.Array         # (n_blocks, n_levels) f32/bf16
    bits: int                 # target bit-width b
    block: int                # block size (64) or -1 for per-tensor
    dtype: object             # dequantized dtype
    shard: Optional[str] = None   # None | "n" | "k" | "e" | "v"

    @property
    def shape(self):
        return self.codes.shape

    @property
    def n_levels(self):
        return self.scales.shape[-1]

    def tree_flatten(self):
        return ((self.codes, self.scales),
                (self.bits, self.block, self.dtype, self.shard))

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, scales = children
        bits, block, dtype, shard = aux
        return cls(codes, scales, bits, block, dtype, shard)

    def dequantize(self):
        return dequantize(self)


def _solve(blocks, g, solver, lam, iters):
    if solver in ("dp", "kmeans", "wdp"):
        return grouping.solve_blocks(blocks, g, method=solver, lam=lam, iters=iters)
    if solver in ("wgm", "gg", "dg", "wgm_lo"):
        # paper-faithful CPU solvers (offline path) — NumPy, per block
        from . import reference
        blocks_np = np.asarray(blocks, dtype=np.float64)
        levels = np.zeros(blocks_np.shape, dtype=np.int32)
        scales = np.zeros((blocks_np.shape[0], g), dtype=np.float32)
        for i, blk in enumerate(blocks_np):
            if solver == "gg":
                b, order = reference.greedy_grouping(blk, g, lam=lam)
            elif solver == "dg":
                b, order, _ = reference.dynamic_grouping(blk, g, lam=lam)
            elif solver == "wgm_lo":
                b, order = reference.wgm_local_opt(blk, g, lam=lam)
            else:
                w = max(1, blk.size // 256) if blk.size > 4096 else 1
                b, order = reference.windowed_greedy_merging(blk, g, window=w, lam=lam)
            _, lv, sc = reference.reconstruct(blk, b, order, n_levels=g)
            levels[i] = lv
            scales[i, : len(sc)] = sc
        return jnp.asarray(levels), jnp.asarray(scales)
    raise ValueError(f"unknown solver: {solver}")


def _nearest_refine(blocks, levels, scales):
    """Re-encode each weight to the *nearest* codebook scale.

    Deployment encode given a fixed codebook {alpha_z}: interval assignment
    from a heuristic solver can be improved by nearest-scale assignment
    (~15% MSE for the greedy solvers; a no-op at the DP optimum, which is a
    Lloyd fixed point). Beyond-paper refinement, property-tested to never
    increase the error.
    """
    mags = jnp.abs(blocks)                                 # (nb, bs)
    d = jnp.abs(mags[:, :, None] - scales[:, None, :])     # (nb, bs, g)
    # empty groups carry scale 0 — exclude them unless the weight is 0
    valid = (scales > 0)[:, None, :] | (mags[:, :, None] == 0)
    d = jnp.where(valid, d, jnp.inf)
    return jnp.argmin(d, axis=-1).astype(levels.dtype)


def quantize_blockwise(w, bits=4, block=64, solver="dp", lam=0.0, iters=30,
                       scale_dtype=jnp.float32, refine=False):
    """4-bit (default) block-wise MSB quantization: 64-element groups per row.

    ``w`` is reshaped to (n_blocks, block) along its last axis (the paper's
    64-elements-per-row blocks). Last axis must be divisible by ``block``.
    ``refine`` re-encodes each weight to its nearest codebook scale
    (improves the heuristic solvers; no-op for the exact DP).

    Its three phases are host spans on a running ``jax.profiler`` trace
    (``quantize.prepare``, ``quantize.solve``, ``quantize.encode``), so an
    idle device gap between their programs names the host work in it.
    """
    w = jnp.asarray(w)
    if w.shape[-1] % block:
        raise ValueError(f"last dim {w.shape[-1]} not divisible by block {block}")
    g = 2 ** (bits - 1)
    with TraceAnnotation("quantize.prepare"):
        blocks = w.reshape(-1, block).astype(jnp.float32)
    with TraceAnnotation("quantize.solve"):
        levels, scales = _solve(blocks, g, solver, lam, iters)
        if refine:
            levels = _nearest_refine(blocks, levels, scales)
    with TraceAnnotation("quantize.encode"):
        signs = jnp.sign(blocks).astype(jnp.int8)
        codes = (signs * (levels.astype(jnp.int8) + 1)).reshape(w.shape)
        # scales keep the weight's batch dims so stacked (scan-over-layers)
        # params stay scannable: (..., last//block, g)
        scales = scales.reshape(*w.shape[:-1], w.shape[-1] // block, g)
        scales = scales.astype(scale_dtype)
    return QTensor(codes, scales, bits, block, w.dtype)


def quantize_pertensor(w, bits=6, solver="wdp", lam=0.0, iters=50,
                       scale_dtype=jnp.float32):
    """6-bit (default) per-tensor MSB quantization: one global codebook."""
    w = jnp.asarray(w)
    g = 2 ** (bits - 1)
    flat = w.reshape(1, -1).astype(jnp.float32)
    levels, scales = _solve(flat, g, solver, lam, iters)
    signs = jnp.sign(flat).astype(jnp.int8)
    codes = (signs * (levels.astype(jnp.int8) + 1)).reshape(w.shape)
    return QTensor(codes, scales.astype(scale_dtype), bits, -1, w.dtype)


def dequantize(q: QTensor):
    """w_hat = sign(code) * scales[block, |code| - 1]; exact 0 for code 0.

    Works for any scales layout (..., g): each scale row covers
    codes.size / n_rows consecutive codes (64 block-wise, a whole matrix
    per-tensor, a layer slice for stacked per-tensor params).
    """
    codes = q.codes
    g = q.scales.shape[-1]
    rows = q.scales.size // g
    blocks = codes.reshape(rows, -1)
    lv = jnp.abs(blocks).astype(jnp.int32)
    scales2d = q.scales.reshape(rows, g).astype(jnp.float32)
    mag = jnp.take_along_axis(scales2d, jnp.maximum(lv - 1, 0), axis=1)
    mag = jnp.where(lv > 0, mag, 0.0)
    out = jnp.sign(blocks).astype(jnp.float32) * mag
    return out.reshape(codes.shape).astype(q.dtype)


# ---------------------------------------------------------------------------
# int4 packing (deployment path for the Pallas kernel)
# ---------------------------------------------------------------------------

PACK_BLOCK = 64      # MSB block size — matches kernels/msb_matmul BLOCK
PACK_LEVELS = 8      # 2^(4-1) codebook entries per block


def pack_codes_int4(codes):
    """Pack 4-bit MSB codes two-per-byte along the last axis.

    nibble = (sign_bit << 3) | level, level in [0, 8). Exact zeros (code 0)
    pack as level 0 / sign + (they dequantize to +alpha_0 — the packed path
    trades the zero special-case for density; see DESIGN.md Sec. 7).

    Layout (DESIGN.md Sec. 9): within each 64-element block of the last
    axis, byte ``j`` holds element ``j`` in its low nibble and element
    ``j + 32`` in its high nibble. The two nibbles of a packed row thus
    fill two contiguous 32-wide halves of the block, so the kernel unpacks
    with lane slices and a concatenation — never a lane interleave, which
    the TPU compiler does not lower in reasonable time.
    """
    if codes.ndim == 0 or codes.shape[-1] % PACK_BLOCK:
        raise ValueError(f"packing needs a last dim divisible by "
                         f"{PACK_BLOCK}, got shape {codes.shape}")
    c32 = codes.astype(jnp.int32)
    lv = jnp.maximum(jnp.abs(c32) - 1, 0)
    nib = ((c32 < 0).astype(jnp.int32) << 3) | lv
    halves = nib.reshape(*nib.shape[:-1], -1, 2, PACK_BLOCK // 2)
    byte = (halves[..., 1, :] << 4) | halves[..., 0, :]
    return byte.reshape(*nib.shape[:-1], -1).astype(jnp.uint8)


def _unpack_nibble_codes(packed):
    """uint8 (..., half) -> nibbles int32 (..., 2*half); inverse layout of
    ``pack_codes_int4``."""
    p32 = packed.astype(jnp.int32)
    blocks = p32.reshape(*p32.shape[:-1], -1, PACK_BLOCK // 2)
    nib = jnp.stack([blocks & 0xF, (blocks >> 4) & 0xF], axis=-2)
    return nib.reshape(*p32.shape[:-1], 2 * p32.shape[-1])


def unpack_codes_int4(packed, shape):
    nib = _unpack_nibble_codes(packed)
    lv = (nib & 0x7).astype(jnp.int8) + 1
    sign = jnp.where((nib >> 3) & 1 > 0, jnp.int8(-1), jnp.int8(1))
    return (sign * lv).reshape(shape)


# ---------------------------------------------------------------------------
# Packed execution format (deployment path; DESIGN.md Sec. 9)
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class PackedQTensor:
    """Kernel-layout MSB tensor: 4-bit codes two-per-byte + 3-D codebooks.

    Storage is in *matmul orientation* ``(..., K, N)``: ``y = x @ dequant``.
    The last dim is padded to a multiple of ``PACK_BLOCK`` at pack time
    (``n`` keeps the logical width; padded columns carry zero scales and
    dequantize to exact 0, so the matmul wrapper just slices them off).

    Two scale layouts, both one 8-entry codebook per 64-element block, with
    the codebook axis second to last and a long axis last. TPU tiles the two
    minor dims of an array as (8, 128), so this layout is dense in HBM and
    in VMEM; a trailing codebook axis of 8 would be padded 16x wherever the
    kernel needs its operand in row-major layout.
      * n-blocked (default): blocks run along N — ``scales`` is
        ``(..., N_pad // 64, 8, K)``. This is every dense (in, out) weight.
      * k-blocked (``kblocked=True``): blocks run along K — ``scales`` is
        ``(..., K // 64, 8, N_pad)``. Produced by ``transpose=True`` packing
        of a ``(V, D)`` table so the unembedding projection
        ``x (B, D) @ table^T (D, V)`` hits the fused kernel without
        re-quantizing: the original block-along-D grouping *is* the
        block-along-K grouping of the transposed operand.

    Like ``QTensor`` it is a pytree (packed/scales leaves; bits/block/dtype/
    n/kblocked/shard static), so stacked scan-over-layers params slice
    cleanly and the static aux never retraces.

    ``shard`` is static tensor-parallel metadata (see ``QTensor``): ``"n"``
    = N (output) sharded across the mesh's model axis, ``"k"`` = K (row)
    sharded (consumer psums partial products), ``"e"`` = expert dim
    sharded. Inside ``shard_map`` the leaves are per-rank slices while the
    static ``n`` still records the *global* padded width — the engines run
    ``core.policy.tp_localize`` on the local tree to rebind ``n`` to the
    local shard width before any matmul.
    """
    packed: jax.Array         # uint8 (..., K, N_pad // 2)
    scales: jax.Array         # see class docstring
    bits: int
    block: int
    dtype: object
    n: int                    # logical N before padding
    kblocked: bool = False
    shard: Optional[str] = None   # None | "n" | "k" | "e"

    @property
    def shape(self):
        return self.packed.shape[:-1] + (self.n,)

    @property
    def n_pad(self):
        return self.packed.shape[-1] * 2

    def tree_flatten(self):
        return ((self.packed, self.scales),
                (self.bits, self.block, self.dtype, self.n, self.kblocked,
                 self.shard))

    @classmethod
    def tree_unflatten(cls, aux, children):
        packed, scales = children
        bits, block, dtype, n, kblocked, shard = aux
        return cls(packed, scales, bits, block, dtype, n, kblocked, shard)

    def dequantize(self):
        return packed_dequantize(self)


def _pad_last(a, to):
    pad = (-a.shape[-1]) % to
    if not pad:
        return a
    widths = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
    return jnp.pad(a, widths)


def pack_qtensor(q: QTensor, *, transpose=False) -> PackedQTensor:
    """QTensor -> kernel storage layout, once at load time.

    ``transpose=True`` packs a 2-D ``(V, D)`` table as its transpose
    ``(D, V)`` with k-blocked scales (unembedding orientation). Requires
    4-bit block-64 quantization; N not divisible by the block is padded
    with zero-scale columns.
    """
    if q.bits != 4 or q.block != PACK_BLOCK:
        raise ValueError(f"packing needs 4-bit block-{PACK_BLOCK} "
                         f"quantization, got {q.bits}-bit block {q.block}")
    codes, scales = q.codes, q.scales
    if transpose:
        if codes.ndim != 2:
            raise ValueError("transpose packing is for 2-D tables")
        v, d = codes.shape
        codes = codes.T                                     # (D, V)
        n = v
        codes = _pad_last(codes, PACK_BLOCK)
        # (V, D//64, 8) -> (D//64, 8, V), pad V with zero-scale columns
        scales = _pad_last(jnp.transpose(scales, (1, 2, 0)), PACK_BLOCK)
    else:
        n = codes.shape[-1]
        codes = _pad_last(codes, PACK_BLOCK)
        pad_blocks = (codes.shape[-1] - n) // PACK_BLOCK
        if pad_blocks:
            widths = [(0, 0)] * (scales.ndim - 2) + [(0, pad_blocks), (0, 0)]
            scales = jnp.pad(scales, widths)
        # (..., K, N_pad//64, 8) -> (..., N_pad//64, 8, K)
        scales = jnp.moveaxis(scales, -3, -1)
    packed = pack_codes_int4(codes)
    return PackedQTensor(packed, scales, q.bits, q.block, q.dtype, n,
                         kblocked=transpose)


def _unpack_nibbles(packed):
    """uint8 (..., half) -> (level int32, sign f32), both (..., 2*half)."""
    nib = _unpack_nibble_codes(packed)
    level = nib & 0x7
    sign = (1 - 2 * ((nib >> 3) & 1)).astype(jnp.float32)
    return level, sign


def packed_dequantize(pq: PackedQTensor):
    """Dense weights in matmul orientation ``(..., K, n)``.

    jnp fallback for backends without the fused kernel; mirrors the exact
    f32 take-along-axis math of ``dequantize`` so packed and simulated
    execution agree bit-for-bit (up to the packed-zero caveat, DESIGN.md
    Sec. 7).
    """
    level, sign = _unpack_nibbles(pq.packed)       # (..., K, N_pad)
    n_pad = level.shape[-1]
    sc = pq.scales.astype(jnp.float32)
    if pq.kblocked:
        # sc (..., K//64, 8, N_pad): block index = k // 64. Gather per
        # (k-block, n) with levels brought to (..., K//64, N_pad, 64).
        k = level.shape[-2]
        lv = level.reshape(*level.shape[:-2], k // PACK_BLOCK, PACK_BLOCK,
                           n_pad)
        lvb = jnp.moveaxis(lv, -2, -1)             # (..., K//64, N_pad, 64)
        magb = jnp.take_along_axis(jnp.swapaxes(sc, -1, -2), lvb, axis=-1)
        mag = jnp.moveaxis(magb, -1, -2).reshape(*level.shape)
    else:
        # sc (..., N_pad//64, 8, K): block index = n // 64
        lv = level.reshape(*level.shape[:-1], n_pad // PACK_BLOCK, PACK_BLOCK)
        mag = jnp.take_along_axis(jnp.moveaxis(sc, -1, -3), lv,
                                  axis=-1).reshape(*level.shape)
    w = sign * mag
    return w[..., : pq.n].astype(pq.dtype)


def packed_gather(pq: PackedQTensor, idx):
    """Rows ``idx`` of a natural-orientation packed table, dequantized.

    The packed-weight analogue of ``dequantize(q)[idx]``: unpacks and
    dequantizes *only the gathered rows*, so the embedding lookup never
    materializes the full bf16 table (the old simulation path did, every
    step)."""
    if pq.kblocked:
        raise ValueError("packed_gather needs natural (n-blocked) layout")
    rows = jnp.take(pq.packed, idx, axis=0)        # (..., N_pad//2)
    # scales (N_pad//64, 8, V) -> rows (..., N_pad//64, 8)
    srow = jnp.moveaxis(jnp.take(pq.scales, idx, axis=-1), (0, 1), (-2, -1))
    level, sign = _unpack_nibbles(rows)
    n_pad = level.shape[-1]
    lv = level.reshape(*level.shape[:-1], n_pad // PACK_BLOCK, PACK_BLOCK)
    mag = jnp.take_along_axis(srow.astype(jnp.float32), lv,
                              axis=-1).reshape(*level.shape)
    return (sign * mag)[..., : pq.n].astype(pq.dtype)


# ---------------------------------------------------------------------------
# KV-page codec (serving path; DESIGN.md Sec. 15)
#
# MSB block-wise quantization applied to *committed* KV-cache pages: one
# (page_size, head_dim) payload per KV head is split into groups of
# KV_BLOCK elements covering whole token rows, and each group gets its own
# codebook (4-bit: a 2^{b-1}-entry MSB codebook from the exact DP solver;
# 8-bit: a single absmax scale with sign-magnitude int8 codes). Groups
# never cross heads, so head-sharded pools quantize identically per shard,
# and the functions are pure and deterministic — the supervisor's
# token-identical replay holds on quantized pools.
# ---------------------------------------------------------------------------

KV_BLOCK = 128       # max elements per KV quantization group


def _kv_tokens_per_block(page_size, head_dim):
    """Token rows per group: whole-hd rows, <= KV_BLOCK elements, dividing
    the page. Static python ints — resolved at trace time."""
    tpb = max(1, min(int(page_size), KV_BLOCK // int(head_dim)))
    while page_size % tpb:
        tpb -= 1
    return tpb


@dataclasses.dataclass(frozen=True)
class KVQuantSpec:
    """Static shape/byte schema of a quantized KV page pool (hashable, so
    it can ride through jit as a static argument). ``kv_heads`` is the
    *local* head count under tensor parallelism — grouping is per head, so
    the schema shards trivially along the head dim."""
    bits: int            # 8 or 4 (16 = unquantized native pools, no spec)
    page_size: int
    kv_heads: int
    head_dim: int

    def __post_init__(self):
        if self.bits not in (4, 8):
            raise ValueError(f"kv_bits must be 4 or 8, got {self.bits}")
        if self.bits == 4 and self.head_dim % 2:
            raise ValueError("4-bit KV packing needs an even head_dim")

    @property
    def tokens_per_block(self):
        return _kv_tokens_per_block(self.page_size, self.head_dim)

    @property
    def block(self):
        return self.tokens_per_block * self.head_dim

    @property
    def n_blocks(self):
        return self.page_size // self.tokens_per_block

    @property
    def n_levels(self):
        return 2 ** (self.bits - 1) if self.bits == 4 else 1

    @property
    def codes_tail(self):
        """Trailing dims of the codes leaf for one page."""
        hd = self.head_dim // 2 if self.bits == 4 else self.head_dim
        return (self.page_size, self.kv_heads, hd)

    @property
    def scales_tail(self):
        return (self.kv_heads, self.n_blocks, self.n_levels)

    @property
    def scale_dtype(self):
        # 4-bit keeps the paper's 16-bit codebook entries (Sec. 4.1
        # storage math); the 8-bit absmax is one f32 per 128 elements —
        # negligible, and it keeps the round-trip error centred.
        return jnp.bfloat16 if self.bits == 4 else jnp.float32

    def page_bytes(self):
        """Allocated bytes per page per K-or-V tensor (one layer period)."""
        codes = int(np.prod(self.codes_tail))
        scales = (int(np.prod(self.scales_tail))
                  * jnp.dtype(self.scale_dtype).itemsize)
        return codes + scales


def kv_native_page_bytes(page_size, kv_heads, head_dim, dtype):
    """Pool bytes per page per K-or-V tensor at ``kv_bits=16`` (native)."""
    return (int(page_size) * int(kv_heads) * int(head_dim)
            * jnp.dtype(dtype).itemsize)


def _kv_to_blocks(x, tpb):
    """(..., ps, kv, hd) -> (..., kv, n_blocks, tpb*hd)."""
    *lead, ps, kv, hd = x.shape
    y = x.reshape(*lead, ps // tpb, tpb, kv, hd)
    y = jnp.moveaxis(y, -2, -4)                 # (..., kv, nb, tpb, hd)
    return y.reshape(*lead, kv, ps // tpb, tpb * hd)


def _kv_from_blocks(y, tpb, head_dim):
    """(..., kv, n_blocks, tpb*hd) -> (..., ps, kv, hd)."""
    *lead, kv, nb, blk = y.shape
    y = y.reshape(*lead, kv, nb, tpb, head_dim)
    y = jnp.moveaxis(y, -4, -2)                 # (..., nb, tpb, kv, hd)
    return y.reshape(*lead, nb * tpb, kv, head_dim)


def kv_quantize_pages(x, bits):
    """Quantize KV pages. x: (..., page_size, KV, head_dim) f32/bf16.

    Returns ``(codes, scales)``:
      * 4-bit: codes uint8 (..., ps, KV, hd//2) — two MSB nibbles per byte,
        ``(sign << 3) | level`` with element 2i in the low nibble; scales
        bf16 (..., KV, n_blocks, 8), the per-group DP codebook sorted
        ascending (exact zeros group at level 0 with scale 0, so they
        survive the round trip exactly whenever they form their own group).
      * 8-bit: codes int8 (..., ps, KV, hd) sign-magnitude
        ``round(x * 127 / absmax)``; scales f32 (..., KV, n_blocks, 1)
        holding the group absmax (so 0 and +-absmax round-trip exactly).

    Pure and deterministic: same page bytes in, same code bytes out, every
    call — required for supervisor replay (DESIGN.md Sec. 14).
    """
    *lead, ps, kv, hd = x.shape
    tpb = _kv_tokens_per_block(ps, hd)
    nb, blk = ps // tpb, tpb * hd
    xf = x.astype(jnp.float32)
    blocks = _kv_to_blocks(xf, tpb)                     # (..., kv, nb, blk)
    if bits == 8:
        amax = jnp.max(jnp.abs(blocks), axis=-1, keepdims=True)
        q = jnp.clip(jnp.round(blocks * (127.0 / jnp.maximum(amax, 1e-30))),
                     -127.0, 127.0)
        codes = _kv_from_blocks(q, tpb, hd).astype(jnp.int8)
        return codes, amax
    if bits != 4:
        raise ValueError(f"kv_bits must be 4 or 8, got {bits}")
    g = 8
    levels, scales = grouping.solve_blocks(blocks.reshape(-1, blk), g,
                                           method="dp")
    levels = levels.reshape(blocks.shape)
    scales = scales.reshape(*lead, kv, nb, g).astype(jnp.bfloat16)
    nib = jnp.where(blocks < 0, levels | 8, levels)     # sign<<3 | level
    nib = _kv_from_blocks(nib, tpb, hd)                 # (..., ps, kv, hd)
    pair = nib.reshape(*nib.shape[:-1], hd // 2, 2)
    codes = ((pair[..., 1] << 4) | pair[..., 0]).astype(jnp.uint8)
    return codes, scales


def kv_dequantize_pages(codes, scales, bits, dtype):
    """Inverse of ``kv_quantize_pages`` -> (..., ps, KV, hd) in ``dtype``."""
    if bits == 8:
        *lead, ps, kv, hd = codes.shape
        tpb = _kv_tokens_per_block(ps, hd)
        blocks = _kv_to_blocks(codes.astype(jnp.float32), tpb)
        out = blocks * (scales.astype(jnp.float32) / 127.0)
        return _kv_from_blocks(out, tpb, hd).astype(dtype)
    if bits != 4:
        raise ValueError(f"kv_bits must be 4 or 8, got {bits}")
    *lead, ps, kv, hdc = codes.shape
    hd = hdc * 2
    tpb = _kv_tokens_per_block(ps, hd)
    p32 = codes.astype(jnp.int32)
    nib = jnp.stack([p32 & 0xF, (p32 >> 4) & 0xF],
                    axis=-1).reshape(*lead, ps, kv, hd)
    nb_ = _kv_to_blocks(nib, tpb)                       # (..., kv, nb, blk)
    level = nb_ & 0x7
    sign = (1 - 2 * ((nb_ >> 3) & 1)).astype(jnp.float32)
    mag = jnp.take_along_axis(scales.astype(jnp.float32), level, axis=-1)
    return _kv_from_blocks(sign * mag, tpb, hd).astype(dtype)


# ---------------------------------------------------------------------------
# Tensor-parallel padding (DESIGN.md Sec. 10)
#
# Sharding a quantized matmul dim across a mesh axis needs every rank's
# slice to hold whole 64-element MSB blocks (N) or whole rows (K). When the
# stored width does not divide, the storage is widened with *exact-zero*
# columns/rows (zero scales for packed codes, code 0 for int8 codes), which
# contribute nothing to any matmul — the padded tree computes the same
# function as the original on every path, sharded or not.
# ---------------------------------------------------------------------------

def _pad_axis(a, axis, to):
    cur = a.shape[axis]
    if cur == to:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, to - cur)
    return jnp.pad(a, widths)


def tp_pad_packed_n(pq: PackedQTensor, n_to: int) -> PackedQTensor:
    """Widen a PackedQTensor's stored N to ``n_to`` with zero-scale columns.

    ``n`` is rebound to ``n_to``: the padded columns dequantize to exact 0,
    so consumers see extra all-zero output features (harmless for an MLP
    hidden dim, masked for a vocab dim) and downstream K-padded row-parallel
    partners line up with them. Requires whole 64-element blocks
    (``n_to % block == 0`` — block size is even, so byte packing is safe).
    """
    if n_to % pq.block:
        raise ValueError(f"n_to={n_to} must be a multiple of {pq.block}")
    if pq.n_pad > n_to:
        raise ValueError(f"cannot shrink storage {pq.n_pad} -> {n_to}")
    packed = _pad_axis(pq.packed, -1, n_to // 2)
    if pq.kblocked:
        scales = _pad_axis(pq.scales, -1, n_to)
    else:
        scales = _pad_axis(pq.scales, -3, n_to // PACK_BLOCK)
    return dataclasses.replace(pq, packed=packed, scales=scales, n=n_to)


def tp_pad_packed_k(pq: PackedQTensor, k_to: int) -> PackedQTensor:
    """Widen a PackedQTensor's K (row) dim to ``k_to`` with zero-scale rows."""
    if pq.kblocked:
        raise ValueError("K-padding needs the natural n-blocked layout")
    if pq.packed.shape[-2] > k_to:
        raise ValueError(f"cannot shrink K {pq.packed.shape[-2]} -> {k_to}")
    return dataclasses.replace(pq,
                               packed=_pad_axis(pq.packed, -2, k_to),
                               scales=_pad_axis(pq.scales, -1, k_to))


def tp_pad_q_n(q: QTensor, n_to: int) -> QTensor:
    """Widen a block-wise QTensor's last (output) dim with zero-code columns."""
    if n_to % q.block:
        raise ValueError(f"n_to={n_to} must be a multiple of block {q.block}")
    return dataclasses.replace(q,
                               codes=_pad_axis(q.codes, -1, n_to),
                               scales=_pad_axis(q.scales, -2, n_to // q.block))


def tp_pad_q_k(q: QTensor, k_to: int) -> QTensor:
    """Widen a block-wise QTensor's second-to-last (row) dim with zero rows."""
    return dataclasses.replace(q,
                               codes=_pad_axis(q.codes, -2, k_to),
                               scales=_pad_axis(q.scales, -3, k_to))


# ---------------------------------------------------------------------------
# Double quantization (paper Appendix G)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DQScales:
    """Scales quantized once more with WGM/MSB at 6 bits over 2048-blocks."""
    q: QTensor
    shape: tuple

    def dequantize(self):
        return self.q.dequantize().reshape(self.shape)


def double_quantize(q: QTensor, bits=6, block=2048, solver="kmeans"):
    """Quantize the scale table itself (recursive MSB), per Appendix G.

    Storage: 4 + 8*6.25/64 ~ 4.78 bits/weight for the default setting.
    """
    scales = q.scales.reshape(-1)
    pad = (-scales.shape[0]) % block
    padded = jnp.concatenate([scales, jnp.zeros((pad,), scales.dtype)])
    sq = quantize_blockwise(padded.reshape(-1, block), bits=bits, block=block,
                            solver=solver)
    return dataclasses.replace(
        q, scales=sq.dequantize().reshape(-1)[: scales.shape[0]]
                   .reshape(q.scales.shape))


# ---------------------------------------------------------------------------
# Storage accounting (paper Sec. 4.1)
# ---------------------------------------------------------------------------

def storage_bits_per_weight(q, double_quant=False,
                            scale_bits=16, dq_bits=6, dq_block=2048):
    """Effective bits/weight incl. codebook metadata.

    4-bit block-64, bf16 scales: 4 + 8*16/64 = 6.00 (paper).  With DQ:
    4 + 8*(6 + 32*16/2048)/64 = 4.78 (paper App. G). Per-tensor: ~b bits.

    For a ``PackedQTensor`` the answer is the *real allocated footprint*
    (uint8 codes + scale table, incl. any N-padding) over the logical
    element count — what HBM actually holds, not the formula.
    """
    if isinstance(q, PackedQTensor):
        n = float(np.prod(q.shape))
        scale_bits = jnp.dtype(q.scales.dtype).itemsize * 8
        return (q.packed.size * 8 + q.scales.size * scale_bits) / n
    n = float(np.prod(q.shape))
    if q.block == -1:
        return q.bits + q.n_levels * scale_bits / n
    per_scale = (dq_bits + 32 * scale_bits / dq_block) if double_quant else scale_bits
    return q.bits + q.n_levels * per_scale / q.block
