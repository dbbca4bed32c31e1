"""Pallas TPU kernel: fused MSB-dequantize + matmul.

Computes ``y = x @ dequant(Wq) (+ bias)`` where Wq is 4-bit MSB weight
storage:
  packed : uint8 (K, N//2) — two 4-bit codes per byte
           nibble = (sign_bit << 3) | level,  level in [0, 8); byte j of
           each 64-column block holds columns j and j + 32
  scales : bf16/f32, one 8-level codebook per 64-element block, codebook
           axis second to last so HBM and VMEM tiles stay dense:
             n-blocked (dense weights): (N//64, 8, K) — blocks along N
             k-blocked (transposed unembedding): (K//64, 8, N) — blocks
             along K (``kblocked=True``)

TPU mapping (DESIGN.md Sec. 9): the kernel streams x tiles (bm, bk) and
packed-code tiles (bk, bn//2) HBM->VMEM, unpacks + dequantizes in VMEM
registers (3 bit-ops + an 8-way select — no gather), and feeds the MXU with
(bm, bk) x (bk, bn) bf16 tiles, accumulating f32 into the output tile. The
weight HBM traffic is ~6 bits/weight (codes + codebooks) instead of 16 —
the decode-shape memory-roofline win modeled (not measured on a chip) in
BENCH_matmul.json.

Serving shapes: tile sizes default to a (M, K, N)-keyed heuristic
(``pick_blocks``) — bucketed decode (M <= 8) takes a skinny-M/GEMV
specialization (one sublane-high x row tile, wide bn so the packed weight
stream dominates traffic); M is padded to the tile height inside this
wrapper, so any decode bucket or prefill length works. An optional bias
(1, N) is added to the output tile on the last K step (fused — no separate
bias pass over HBM).

Grid: (M/bm, N/bn, K/bk), K innermost for output-tile accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 64     # MSB block size along the quantization axis
LEVELS = 8     # 2^(4-1) scales per block


def _unpack_tile(packed):
    """uint8 (bk, bn//2) -> nibbles int32 (bk, bn), in column order.

    Byte ``j`` of each 32-byte group holds column ``j`` (low nibble) and
    column ``j + 32`` (high nibble) of its 64-column block
    (``core.quantize.pack_codes_int4``), so each block unpacks to two
    contiguous halves and the tile is their lane concatenation."""
    p32 = packed.astype(jnp.int32)
    half = BLOCK // 2
    parts = []
    for b in range(p32.shape[1] // half):
        pb = p32[:, b * half:(b + 1) * half]
        parts += [pb & 0xF, (pb >> 4) & 0xF]
    return jnp.concatenate(parts, axis=1)


def _dequant_tile(packed, scales, *, kblocked, dot_dtype):
    """uint8 (bk, bn//2) + codebook tile -> bf16/f32 (bk, bn) weights."""
    nib = _unpack_tile(packed)
    bk, bn = nib.shape
    level = nib & 0x7                            # (bk, bn)
    sign = (1 - 2 * ((nib >> 3) & 1)).astype(jnp.float32)

    # 8-way select instead of a gather: w = sum_z [level == z] * scales[.., z]
    sc = scales.astype(jnp.float32)
    mag = jnp.zeros((bk, bn), jnp.float32)
    for z in range(LEVELS):
        if kblocked:                             # sc (bk//64, 8, bn)
            sz = jnp.repeat(sc[:, z, :], BLOCK, axis=0)       # (bk, bn)
        else:                                    # sc (bn//64, 8, bk)
            sz = jnp.repeat(sc[:, z, :].T, BLOCK, axis=1)     # (bk, bn)
        mag = mag + jnp.where(level == z, sz, 0.0)
    return (sign * mag).astype(dot_dtype)


def _kernel(x_ref, packed_ref, scales_ref, o_ref, *, bk_steps, dot_dtype,
            kblocked):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = _dequant_tile(packed_ref[...], scales_ref[...],
                      kblocked=kblocked, dot_dtype=dot_dtype)
    acc = jnp.dot(x_ref[...].astype(dot_dtype), w,
                  preferred_element_type=jnp.float32)
    o_ref[...] += acc


def _kernel_bias(x_ref, packed_ref, scales_ref, bias_ref, o_ref, *,
                 bk_steps, dot_dtype, kblocked):
    _kernel(x_ref, packed_ref, scales_ref, o_ref, bk_steps=bk_steps,
            dot_dtype=dot_dtype, kblocked=kblocked)

    @pl.when(pl.program_id(2) == bk_steps - 1)
    def _add_bias():
        o_ref[...] += bias_ref[...].astype(jnp.float32)


def pick_blocks(m, k, n):
    """(bm, bn, bk) for one call, legal for the TPU's block rules.

    A block's last two dims must be multiples of the (8, 128) tile or span
    the whole array dim. So bn is 512 or 256 where it divides N (the packed
    tile's bn/2 bytes fill whole lanes), else all of N; bk is 256 or 128
    where it divides K, else all of K. Decode (M <= 8) is pure weight
    streaming: one sublane-high row tile, with M padded to 8. Larger M
    takes row tiles of up to 128, with M padded to a multiple of the tile.
    """
    bm = 8 if m <= 8 else min(128, -(-m // 8) * 8)
    bn = next((c for c in (512, 256) if n % c == 0), n)
    bk = next((c for c in (256, 128) if k % c == 0), k)
    return bm, bn, bk


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "kblocked",
                                             "interpret"))
def msb_matmul(x, packed, scales, bias=None, *, bm=None, bn=None, bk=None,
               kblocked=False, interpret=False):
    """x: (M, K); packed: (K, N//2) uint8; scales: (N//64, 8, K) n-blocked
    or (K//64, 8, N) k-blocked; bias: optional (N,) or (1, N).

    Returns (M, N) in x.dtype. Tile sizes must be multiples of the MSB
    block along the blocked axis; unset sizes come from ``pick_blocks``.
    M is padded to a multiple of the tile height internally.
    """
    m, k = x.shape
    n = packed.shape[1] * 2
    hm, hn, hk = pick_blocks(m, k, n)
    bm = hm if bm is None else bm
    bn = hn if bn is None else min(bn, n)
    bk = hk if bk is None else min(bk, k)
    if m % bm:
        x = jnp.pad(x, ((0, -m % bm), (0, 0)))
    mp = x.shape[0]
    assert mp % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    assert bn % BLOCK == 0
    if kblocked:
        assert bk % BLOCK == 0, (bk, "k-blocked scales need 64-aligned bk")
    dot_dtype = x.dtype if x.dtype in (jnp.bfloat16, jnp.float32) else jnp.float32

    grid = (mp // bm, n // bn, k // bk)
    scales_spec = (
        pl.BlockSpec((bk // BLOCK, LEVELS, bn), lambda i, j, s: (s, 0, j))
        if kblocked else
        pl.BlockSpec((bn // BLOCK, LEVELS, bk), lambda i, j, s: (j, 0, s)))
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
        pl.BlockSpec((bk, bn // 2), lambda i, j, s: (s, j)),
        scales_spec,
    ]
    args = [x, packed, scales]
    if bias is not None:
        bias = bias.reshape(-1)
        if bias.shape[0] != n:    # logical N < padded storage width
            bias = jnp.pad(bias, (0, n - bias.shape[0]))
        in_specs.append(pl.BlockSpec((1, bn), lambda i, j, s: (0, j)))
        args.append(bias.reshape(1, n))
        kern = _kernel_bias
    else:
        kern = _kernel
    out = pl.pallas_call(
        functools.partial(kern, bk_steps=grid[2], dot_dtype=dot_dtype,
                          kblocked=kblocked),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, n), jnp.float32),
        interpret=interpret,
    )(*args)
    return out[:m].astype(x.dtype)
