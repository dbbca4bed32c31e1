"""Pallas TPU kernel: paged-attention decode (one query token per sequence).

Grid: (batch, kv_heads) — each cell handles one sequence's ``rep`` query
heads for one KV head. The kernel walks the sequence's block table with
``jax.lax.fori_loop`` (dynamic trip count = pages actually used, so short
sequences skip dead pool pages entirely), loading one (page_size, d) K/V
tile per iteration straight from the global pool with a dynamic page index
— the gather the pure-JAX reference (models.attention.paged_attention)
materializes as a (B, S_max, KV, d) copy never exists here.

Layout note: the pool keeps its natural (n_pages, page_size, KV, d) layout;
the BlockSpec collapses the KV dim per grid cell so each cell streams only
its own head's tiles. This is the serving-path stub: correctness-validated
in interpret mode on CPU (tests/test_paged_attention.py); real-TPU tile
tuning (page_size multiples of the 128-lane register, scalar-prefetched
block tables via ``pltpu.PrefetchScalarGridSpec``) is a recorded follow-up
in DESIGN.md §8.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -2.0e38


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, *, ps, softcap,
            scale):
    q = q_ref[...].astype(jnp.float32) * scale            # (rep, d)
    rep, d = q.shape
    kv_len = len_ref[0]
    n_used = (kv_len + ps - 1) // ps                       # dynamic bound

    def body(j, carry):
        acc, m_i, l_i = carry
        page = bt_ref[j]
        kt = k_ref[page]                                   # (ps, d)
        vt = v_ref[page]
        s = jnp.dot(q, kt.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)    # (rep, ps)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * ps + jax.lax.iota(jnp.int32, ps)
        s = jnp.where((kpos < kv_len)[None, :], s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_i - m_new)
        l_new = l_i * corr + jnp.sum(p, axis=-1)
        pv = jnp.dot(p.astype(vt.dtype), vt,
                     preferred_element_type=jnp.float32)
        acc = acc * corr[:, None] + pv
        return acc, m_new, l_new

    acc0 = jnp.zeros((rep, d), jnp.float32)
    m0 = jnp.full((rep,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((rep,), jnp.float32)
    acc, _, l_i = jax.lax.fori_loop(0, n_used, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


def _dequant_tile(codes, scales, kv_bits, ps, hd):
    """In-register MSB dequant of one page tile.

    codes: (ps, hd) int8 (8-bit) or (ps, hd//2) uint8 packed nibbles
    (4-bit); scales: (n_blocks, G) — the page's codebook rows. Returns
    (ps, hd) f32. Mirrors core.quantize.kv_dequantize_pages exactly, so
    kernel and oracle agree bit-for-bit.
    """
    from ...core.quantize import _kv_tokens_per_block
    tpb = _kv_tokens_per_block(ps, hd)
    tok_block = jax.lax.iota(jnp.int32, ps) // tpb          # (ps,)
    srows = jnp.take(scales.astype(jnp.float32), tok_block, axis=0)
    if kv_bits == 8:
        return codes.astype(jnp.float32) * (srows / 127.0)  # srows (ps, 1)
    p32 = codes.astype(jnp.int32)
    nib = jnp.stack([p32 & 0xF, (p32 >> 4) & 0xF],
                    axis=-1).reshape(ps, hd)
    level = nib & 0x7
    sign = (1 - 2 * ((nib >> 3) & 1)).astype(jnp.float32)
    mag = jnp.take_along_axis(srows, level, axis=-1)        # (ps, hd)
    return sign * mag


def _quant_kernel(bt_ref, len_ref, row_ref, q_ref, kc_ref, ks_ref, vc_ref,
                  vs_ref, kh_ref, vh_ref, o_ref, *, ps, hd, kv_bits, softcap,
                  scale):
    q = q_ref[...].astype(jnp.float32) * scale               # (rep, d)
    rep, d = q.shape
    kv_len = len_ref[0]
    hot_row = row_ref[0]
    n_full = kv_len // ps                                    # committed pages
    n_used = (kv_len + ps - 1) // ps

    def body(j, carry):
        acc, m_i, l_i = carry
        page = bt_ref[j]
        kt_q = _dequant_tile(kc_ref[page], ks_ref[page], kv_bits, ps, hd)
        vt_q = _dequant_tile(vc_ref[page], vs_ref[page], kv_bits, ps, hd)
        kh = kh_ref[hot_row].astype(jnp.float32)
        vh = vh_ref[hot_row].astype(jnp.float32)
        # page j == n_full is the partial frontier page: full precision
        # from the hot row; committed pages stream dequantized
        is_tail = j >= n_full
        kt = jnp.where(is_tail, kh, kt_q)
        vt = jnp.where(is_tail, vh, vt_q)
        s = jnp.dot(q, kt.T, preferred_element_type=jnp.float32)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = j * ps + jax.lax.iota(jnp.int32, ps)
        s = jnp.where((kpos < kv_len)[None, :], s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_i - m_new)
        l_new = l_i * corr + jnp.sum(p, axis=-1)
        pv = jnp.dot(p, vt, preferred_element_type=jnp.float32)
        acc = acc * corr[:, None] + pv
        return acc, m_new, l_new

    acc0 = jnp.zeros((rep, d), jnp.float32)
    m0 = jnp.full((rep,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((rep,), jnp.float32)
    acc, _, l_i = jax.lax.fori_loop(0, n_used, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("kv_bits", "softcap", "scale",
                                             "interpret"))
def paged_attention_decode_quant(q, k_codes, k_scales, v_codes, v_scales,
                                 k_hot, v_hot, block_tables, kv_lens,
                                 hot_rows, *, kv_bits, softcap=0.0,
                                 scale=None, interpret=False):
    """Fused dequant paged-attention decode over quantized pools.

    q: (B, H, d); k_codes/v_codes: (n_pages, ps, KV, hd or hd//2);
    k_scales/v_scales: (n_pages, KV, n_blocks, G); k_hot/v_hot:
    (n_hot, ps, KV, hd) full-precision partial pages; block_tables:
    (B, max_pages) int32; kv_lens: (B,) int32; hot_rows: (B,) int32 — the
    row of the hot pool holding each sequence's partial page (slot + 1;
    0 = scratch). Returns (B, H, d).

    Committed pages stream through the MSB dequant *in-kernel* — the bf16
    copy of the pool the jnp oracle materializes never exists here. The
    frontier page (j == kv_len // ps) reads the hot row instead.
    """
    b, h, d = q.shape
    n_pages, ps, kv = k_codes.shape[:3]
    nb, g = k_scales.shape[2], k_scales.shape[3]
    n_hot = k_hot.shape[0]
    rep = h // kv
    scale = float(scale if scale is not None else d ** -0.5)
    qr = q.reshape(b, kv, rep, d)
    lens2d = kv_lens.reshape(b, 1).astype(jnp.int32)
    rows2d = hot_rows.reshape(b, 1).astype(jnp.int32)
    mp = block_tables.shape[1]
    hdc = k_codes.shape[3]

    grid = (b, kv)
    out = pl.pallas_call(
        functools.partial(_quant_kernel, ps=ps, hd=d, kv_bits=kv_bits,
                          softcap=float(softcap), scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, mp), lambda i, j: (i, 0)),           # tables
            pl.BlockSpec((None, 1), lambda i, j: (i, 0)),            # lens
            pl.BlockSpec((None, 1), lambda i, j: (i, 0)),            # hot rows
            pl.BlockSpec((None, None, rep, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((n_pages, ps, None, hdc), lambda i, j: (0, 0, j, 0)),
            pl.BlockSpec((n_pages, None, nb, g), lambda i, j: (0, j, 0, 0)),
            pl.BlockSpec((n_pages, ps, None, hdc), lambda i, j: (0, 0, j, 0)),
            pl.BlockSpec((n_pages, None, nb, g), lambda i, j: (0, j, 0, 0)),
            pl.BlockSpec((n_hot, ps, None, d), lambda i, j: (0, 0, j, 0)),
            pl.BlockSpec((n_hot, ps, None, d), lambda i, j: (0, 0, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, rep, d),
                               lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, rep, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lens2d, rows2d, qr, k_codes, k_scales,
      v_codes, v_scales, k_hot, v_hot)
    return out.reshape(b, h, d)


@functools.partial(jax.jit, static_argnames=("softcap", "scale", "interpret"))
def paged_attention_decode(q, k_pool, v_pool, block_tables, kv_lens, *,
                           softcap=0.0, scale=None, interpret=False):
    """q: (B, H, d); pools: (n_pages, page_size, KV, d); block_tables:
    (B, max_pages) int32; kv_lens: (B,) int32. Returns (B, H, d)."""
    b, h, d = q.shape
    n_pages, ps, kv, _ = k_pool.shape
    rep = h // kv
    scale = float(scale if scale is not None else d ** -0.5)
    qr = q.reshape(b, kv, rep, d)
    lens2d = kv_lens.reshape(b, 1).astype(jnp.int32)
    mp = block_tables.shape[1]

    grid = (b, kv)
    out = pl.pallas_call(
        functools.partial(_kernel, ps=ps, softcap=float(softcap),
                          scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, mp), lambda i, j: (i, 0)),           # tables
            pl.BlockSpec((None, 1), lambda i, j: (i, 0)),            # lens
            pl.BlockSpec((None, None, rep, d), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((n_pages, ps, None, d), lambda i, j: (0, 0, j, 0)),
            pl.BlockSpec((n_pages, ps, None, d), lambda i, j: (0, 0, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, rep, d),
                               lambda i, j: (i, j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, rep, d), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lens2d, qr, k_pool, v_pool)
    return out.reshape(b, h, d)
