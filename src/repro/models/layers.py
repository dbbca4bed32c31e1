"""Shared transformer building blocks (pure functions, params as dicts).

Conventions:
  * master params f32; compute in cfg.dtype (bf16) with f32 norms/softmax
  * weights are plain 2-D (in, out) matrices; attention heads live in the
    fused (H*hd) dim so FSDP x TP sharding is uniform (DESIGN.md Sec. 4)
  * every function takes (params, x, cfg, ...) and returns arrays — no
    classes, no framework magic; scan-over-layers stacks a leading L dim
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .attention import (decode_attention, flash_attention, paged_attention,
                        paged_attention_packed, paged_attention_quant,
                        paged_attention_quant_packed, paged_write,
                        paged_write_packed, paged_write_quant,
                        paged_write_quant_packed)


def rms_norm(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(x.dtype)


def rope(x, positions, theta=10000.0):
    """Rotary embeddings. x: (..., S, H, d) or (..., H, d); positions (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def dense(x, w, b=None, tp=None):
    """y = x @ w (+ b), dispatching on the weight representation.

    ``tp`` (a ``parallel.TPShard``, only inside shard_map) makes the matmul
    shard-aware: a K- (row-) sharded quantized weight yields partial
    products that are psummed over ``tp.axis`` before the bias is added;
    N- (column-) sharded weights need nothing — the caller works on the
    local feature slice.
    """
    from ..core.quantize import PackedQTensor, QTensor
    psum_axis = (tp.axis if tp is not None
                 and getattr(w, "shard", None) == "k" else None)
    if isinstance(w, PackedQTensor):  # packed execution: fused kernel on TPU
        from ..kernels.msb_matmul.ops import packed_matmul
        return packed_matmul(x, w, bias=b, psum_axis=psum_axis)
    if isinstance(w, QTensor):      # MSB-quantized serving (simulation mode)
        w = w.dequantize()
    y = jnp.einsum("...d,df->...f", x, w.astype(x.dtype))
    if psum_axis is not None:
        y = jax.lax.psum(y, psum_axis)
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


# ---------------------------------------------------------------------------
# Attention layer (GQA + RoPE + sliding window + softcap + optional bias)
# ---------------------------------------------------------------------------

def _qkv_axes(cfg, parallel):
    """Attention TP policy: shard heads when kv_heads divides tp; otherwise
    context parallelism (_cp_attention) shards the *query sequence*.

    head_dim sharding is expressible but generates an all-reduce of the f32
    score tensor per (q,kv) chunk pair, and full replication duplicates the
    quadratic prefill work tp-x — both measured in EXPERIMENTS.md §Perf
    (iterations 2-3); CP costs one K/V all-gather per layer instead.
    """
    if parallel is None:
        return None
    if cfg.n_kv_heads % parallel.tp_size == 0:
        return ("batch", None, "heads", None)
    return None


def _cp_attention(q, k, v, parallel, *, causal, window, softcap, scale,
                  chunk):
    """Context-parallel flash attention: q seq sharded over `model`; K/V
    all-gathered per layer (their seq enters sharded — the transpose is a
    reduce-scatter on dk/dv). Each rank computes its S/tp query rows against
    the full K/V with globally-correct causal offsets."""
    import jax as _jax
    P = _jax.sharding.PartitionSpec
    tp = parallel.tp_axis
    dp = parallel.dp_axes

    def inner(q_l, k_l, v_l):
        k_f = _jax.lax.all_gather(k_l, tp, axis=1, tiled=True)
        v_f = _jax.lax.all_gather(v_l, tp, axis=1, tiled=True)
        off = _jax.lax.axis_index(tp) * q_l.shape[1]
        return flash_attention(q_l, k_f, v_f, causal=causal, window=window,
                               softcap=softcap, scale=scale, chunk_q=chunk,
                               chunk_kv=chunk, q_offset=off)

    spec = P(dp, tp, None, None)
    return _jax.shard_map(inner, mesh=parallel.mesh,
                          in_specs=(spec, spec, spec), out_specs=spec,
                          check_vma=False)(q, k, v)


def _attn_out_proj(out, wo, tp, full_h):
    """Output projection (B, T, h_local, hd) -> (B, T, D), TP-aware.

    Row- (K-) sharded ``wo`` consumes the local heads directly and ``dense``
    psums the partial products. A *replicated* ``wo`` after head-sliced
    attention first all-gathers the heads (rank-major == global head order),
    which reproduces the single-device activations bit-for-bit.
    """
    b, t = out.shape[0], out.shape[1]
    if (tp is not None and out.shape[2] != full_h
            and getattr(wo, "shard", None) != "k"):
        out = jax.lax.all_gather(out, tp.axis, axis=2, tiled=True)
    return dense(out.reshape(b, t, -1), wo, tp=tp)


def attention_layer(p, x, cfg, positions, *, window=0, cache=None,
                    cur_pos=None, xattn_kv=None, causal=True, cross=False,
                    decode_positions=None, parallel=None, paged=None):
    """Self- or cross-attention.

    Training/prefill: cache is None -> flash attention over the sequence;
    returns the (roped) k/v as the cache for subsequent decode.
    Decode: cache = dict(k, v) ring buffers; ``decode_positions`` (B, S) is
    the *shared* per-entry position table maintained once at the model level.
    Paged serving: ``paged`` = dict(block_tables, q_pos, kv_lens) and cache
    holds the global page pools {k, v}: (n_pages, page_size, KV, hd); the
    new roped k/v is scattered into the pools at q_pos and attention gathers
    each sequence's pages (decode AND chunked prefill use this one path).
    Cross-attention decode (``cross=True``): cache holds the static encoder
    k/v from prefill.

    ``parallel`` is either a ``ParallelContext`` (GSPMD constraints on
    global arrays) or a ``TPShard`` (manual tensor parallelism inside
    shard_map; DESIGN.md Sec. 10). Under a TPShard, column-sharded QKV
    projections produce this rank's heads directly; with replicated
    projections over a head-sharded page pool the computed heads are sliced
    by ``axis_index``. Either way cache/pool leaves hold KV//tp heads and
    the output projection psums (row-sharded wo) or all-gathers heads.
    Returns (out, new_cache).
    """
    from ..parallel.sharding import TPShard, constraint
    tp = parallel if isinstance(parallel, TPShard) else None
    spmd = None if tp is not None else parallel
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    b = x.shape[0]
    w_sharded = tp is not None and getattr(p["wq"], "shard", None) == "n"
    h_l, kv_l = (h // tp.size, kv // tp.size) if w_sharded else (h, kv)
    qkv_ax = _qkv_axes(cfg, spmd)
    q = dense(x, p["wq"], p.get("bq"), tp=tp).reshape(b, -1, h_l, hd)
    if qkv_ax:
        q = constraint(q, qkv_ax, spmd)
    softcap = cfg.attn_softcap
    scale = cfg.head_dim_ ** -0.5 if cfg.query_scale == 0 else cfg.query_scale

    if paged is not None:
        q_pos = paged["q_pos"]
        k = dense(x, p["wk"], p.get("bk"), tp=tp).reshape(b, -1, kv_l, hd)
        v = dense(x, p["wv"], p.get("bv"), tp=tp).reshape(b, -1, kv_l, hd)
        if (tp is not None and not w_sharded
                and h % tp.size == 0 and kv % tp.size == 0):
            # replicated projections over a head-sharded page pool: every
            # rank computes all heads, keeps its contiguous slice
            r = jax.lax.axis_index(tp.axis)
            h_l, kv_l = h // tp.size, kv // tp.size
            q = jax.lax.dynamic_slice_in_dim(q, r * h_l, h_l, axis=2)
            k = jax.lax.dynamic_slice_in_dim(k, r * kv_l, kv_l, axis=2)
            v = jax.lax.dynamic_slice_in_dim(v, r * kv_l, kv_l, axis=2)
        if cfg.use_rope:
            safe_pos = jnp.maximum(q_pos, 0)
            q = rope(q, safe_pos, cfg.rope_theta)
            k = rope(k, safe_pos, cfg.rope_theta)
        seg_ids = paged.get("seg_ids")
        if seg_ids is not None:
            # packed ragged prefill: one (1, T) row carrying several
            # segments; block_tables is (S, max_pages), per-token seg ids
            # route every write/gather to the token's own segment
            if "k_codes" in cache:
                new_cache = paged_write_quant_packed(
                    cache, k, v, paged["block_tables"], seg_ids, q_pos,
                    paged["kv_lens"], paged["slots"], paged["seg_off"],
                    paged["kv_bits"])
                out = paged_attention_quant_packed(
                    q, new_cache, paged["block_tables"], seg_ids, q_pos,
                    paged["kv_lens"], paged["slots"], paged["kv_bits"],
                    window=window, softcap=softcap, scale=scale)
                return _attn_out_proj(out, p["wo"], tp, h), new_cache
            k_pool, v_pool = paged_write_packed(
                cache["k"], cache["v"], k, v, paged["block_tables"],
                seg_ids, q_pos)
            out = paged_attention_packed(
                q, k_pool, v_pool, paged["block_tables"], seg_ids, q_pos,
                paged["kv_lens"], window=window, softcap=softcap,
                scale=scale)
            return (_attn_out_proj(out, p["wo"], tp, h),
                    {"k": k_pool, "v": v_pool})
        if "k_codes" in cache:
            # quantized pools (kv_bits < 16): hot-page write + commit-time
            # quantization, attention fuses dequant into the gather
            new_cache = paged_write_quant(
                cache, k, v, paged["block_tables"], q_pos, paged["kv_lens"],
                paged["slots"], paged["kv_bits"])
            out = paged_attention_quant(
                q, new_cache, paged["block_tables"], q_pos, paged["kv_lens"],
                paged["slots"], paged["kv_bits"], window=window,
                softcap=softcap, scale=scale)
            return _attn_out_proj(out, p["wo"], tp, h), new_cache
        k_pool, v_pool = paged_write(cache["k"], cache["v"], k, v,
                                     paged["block_tables"], q_pos)
        out = paged_attention(q, k_pool, v_pool, paged["block_tables"],
                              q_pos, paged["kv_lens"], window=window,
                              softcap=softcap, scale=scale)
        return (_attn_out_proj(out, p["wo"], tp, h),
                {"k": k_pool, "v": v_pool})

    if cache is None:
        kv_src = xattn_kv if xattn_kv is not None else x
        k = dense(kv_src, p["wk"], p.get("bk"), tp=tp).reshape(b, -1, kv_l, hd)
        v = dense(kv_src, p["wv"], p.get("bv"), tp=tp).reshape(b, -1, kv_l, hd)
        if qkv_ax:
            k = constraint(k, qkv_ax, spmd)
            v = constraint(v, qkv_ax, spmd)
        if xattn_kv is None and cfg.use_rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        use_cp = (spmd is not None and qkv_ax is None
                  and q.shape[1] % (spmd.tp_size
                                    * min(cfg.attn_chunk, 64)) == 0
                  and k.shape[1] % spmd.tp_size == 0)
        if use_cp:
            chunk = min(cfg.attn_chunk, q.shape[1] // spmd.tp_size)
            out = _cp_attention(q, k, v, spmd,
                                causal=causal and xattn_kv is None,
                                window=window, softcap=softcap, scale=scale,
                                chunk=chunk)
        else:
            out = flash_attention(q, k, v,
                                  causal=causal and xattn_kv is None,
                                  window=window, softcap=softcap, scale=scale,
                                  chunk_q=cfg.attn_chunk,
                                  chunk_kv=cfg.attn_chunk)
        new_cache = {"k": k, "v": v}
    elif cross:
        # decode against static encoder k/v (all positions valid)
        s = cache["k"].shape[1]
        pos_tab = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        out = decode_attention(q, cache["k"], cache["v"], pos_tab,
                               jnp.full((b,), s - 1, jnp.int32),
                               window=0, softcap=softcap, scale=scale,
                               chunk_kv=cfg.decode_chunk)
        new_cache = cache
    else:
        # self-attention decode, ring-buffer cache (rope applied at write)
        s = cache["k"].shape[1]
        k = dense(x, p["wk"], p.get("bk"), tp=tp).reshape(b, -1, kv_l, hd)
        v = dense(x, p["wv"], p.get("bv"), tp=tp).reshape(b, -1, kv_l, hd)
        if cfg.use_rope:
            q = rope(q, cur_pos[:, None], cfg.rope_theta)
            k = rope(k, cur_pos[:, None], cfg.rope_theta)
        slot = (cur_pos % s)[0]
        k_cache = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, 1)
        out = decode_attention(q, k_cache, v_cache, decode_positions, cur_pos,
                               window=window, softcap=softcap, scale=scale,
                               chunk_kv=cfg.decode_chunk)
        new_cache = {"k": k_cache, "v": v_cache}
    return _attn_out_proj(out, p["wo"], tp, h), new_cache


# ---------------------------------------------------------------------------
# Dense SwiGLU MLP
# ---------------------------------------------------------------------------

def mlp_layer(p, x, tp=None):
    """SwiGLU MLP. Under a ``TPShard``, wg/wi are column-sharded (local
    hidden slice, padded to whole MSB blocks per rank) and wo row-sharded
    (``dense`` psums the partial products)."""
    gate = jax.nn.silu(dense(x, p["wg"], tp=tp)
                       .astype(jnp.float32)).astype(x.dtype)
    up = dense(x, p["wi"], tp=tp)
    return dense(gate * up, p["wo"], tp=tp)


# ---------------------------------------------------------------------------
# Chunked cross-entropy (recompute logits in backward; never materialize
# the full (B, S, V) logits — DESIGN.md Sec. 7)
# ---------------------------------------------------------------------------

def chunked_softmax_xent(hidden, unembed_vd, labels, mask, chunk=512,
                         softcap=0.0, z_loss=1e-4, vocab_real=None):
    """Mean CE over masked positions. hidden (B,S,D), labels/mask (B,S),
    unembed_vd (V_padded, D). Padded vocab rows are masked to -inf."""
    b, s, d = hidden.shape
    vp = unembed_vd.shape[0]
    c = min(chunk, s)
    while s % c:
        c -= 1
    n = s // c
    hr = hidden.reshape(b, n, c, d)
    lr = labels.reshape(b, n, c)
    mr = mask.reshape(b, n, c)

    @jax.checkpoint
    def chunk_loss(hc, lc, mc):
        logits = jnp.einsum("bcd,vd->bcv", hc.astype(jnp.float32),
                            unembed_vd.astype(jnp.float32))
        if softcap > 0:
            logits = softcap * jnp.tanh(logits / softcap)
        if vocab_real is not None and vocab_real < vp:
            logits = jnp.where(jnp.arange(vp) < vocab_real, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        ce = (lse - gold) * mc
        zl = z_loss * (lse ** 2) * mc
        return jnp.sum(ce + zl), jnp.sum(mc)

    def body(carry, idx):
        tot, cnt = carry
        l, m = chunk_loss(hr[:, idx], lr[:, idx], mr[:, idx])
        return (tot + l, cnt + m), None

    (tot, cnt), _ = jax.lax.scan(body, (0.0, 0.0), jnp.arange(n))
    return tot / jnp.maximum(cnt, 1.0)
