"""Pallas TPU kernel: flash attention forward (causal/window/softcap, GQA).

Grid: (batch * kv_heads * rep, Sq/bq) outer; the kernel loops over KV tiles
with ``jax.lax.fori_loop`` keeping the online-softmax state (m, l, acc) in
VMEM. Unlike the pure-JAX path (models/attention.py — also the oracle),
fully-masked KV tiles ahead of the causal frontier are *skipped* via the
loop upper bound, recovering the ~2x causal-waste the dry-run roofline
charges the XLA path for (EXPERIMENTS.md §Perf).

q: (B, H, Sq, d), k/v: (B, KV, Skv, d) — head-major layout so each grid row
streams one head's tiles.

Segment-aware masking (packed ragged prefill, DESIGN.md Sec. 16): pass
``q_segs``/``kv_segs`` of shape (B, Sq)/(B, Skv) and attention is
additionally masked to ``kv_seg == q_seg`` — tokens of different packed
segments attend to each other with exactly zero weight. Segments must be
contiguous along the sequence axis for causal masking to stay per-segment
correct (global order equals local order then); a pad id of -1 on the q
side yields an all-masked row, which the softmax guard sends to zero
output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_NEG_INF = -2.0e38


def _kernel(*refs, bq, bkv, skv, causal, window, softcap, scale, segmented):
    if segmented:
        q_ref, k_ref, v_ref, qs_ref, ks_ref, o_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref = refs
        qs_ref = ks_ref = None
    iq = pl.program_id(1)
    q = q_ref[...].astype(jnp.float32) * scale          # (bq, d)
    d = q.shape[-1]
    qpos = iq * bq + jax.lax.iota(jnp.int32, bq)
    qseg = qs_ref[...] if segmented else None           # (bq,)

    n_kv = skv // bkv
    if causal:
        # skip tiles strictly above the causal frontier
        hi = jnp.minimum(((iq + 1) * bq + bkv - 1) // bkv, n_kv)
    else:
        hi = n_kv

    def body(ik, carry):
        acc, m_i, l_i = carry
        kt = k_ref[pl.ds(ik * bkv, bkv), :]
        vt = v_ref[pl.ds(ik * bkv, bkv), :]
        s = jnp.dot(q, kt.astype(jnp.float32).T,
                    preferred_element_type=jnp.float32)  # (bq, bkv)
        if softcap > 0:
            s = softcap * jnp.tanh(s / softcap)
        kpos = ik * bkv + jax.lax.iota(jnp.int32, bkv)
        mask = jnp.ones((bq, bkv), jnp.bool_)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kpos[None, :] > qpos[:, None] - window
        if segmented:
            kseg = ks_ref[pl.ds(ik * bkv, bkv)]
            # same segment, and neither side a pad (-1): pads never attend
            mask &= (kseg[None, :] == qseg[:, None]) & (qseg[:, None] >= 0)
        s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        # an all-masked row (a pad segment) keeps m at _NEG_INF, making
        # every p term exp(0)=1; zero it so l stays 0 and the output 0
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(m_i - m_new)
        l_new = l_i * corr + jnp.sum(p, axis=-1)
        pv = jnp.dot(p.astype(vt.dtype), vt,
                     preferred_element_type=jnp.float32)
        acc = acc * corr[:, None] + pv
        return acc, m_new, l_new

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq,), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc, m_i, l_i = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    o_ref[...] = (acc / jnp.maximum(l_i, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "scale", "bq", "bkv", "interpret"))
def flash_attention_fwd(q, k, v, q_segs=None, kv_segs=None, *, causal=True,
                        window=0, softcap=0.0, scale=None, bq=128, bkv=128,
                        interpret=False):
    """q: (B, H, Sq, d); k/v: (B, KV, Skv, d). Returns (B, H, Sq, d).
    ``q_segs``/``kv_segs``: optional (B, Sq)/(B, Skv) int32 segment ids —
    pass both or neither; cross-segment attention is masked out."""
    if (q_segs is None) != (kv_segs is None):
        raise ValueError("pass both q_segs and kv_segs, or neither")
    b, h, sq, d = q.shape
    kv, skv = k.shape[1], k.shape[2]
    rep = h // kv
    scale = float(scale if scale is not None else d ** -0.5)
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0
    segmented = q_segs is not None

    qr = q.reshape(b * h, sq, d)
    kr = jnp.repeat(k, rep, axis=1).reshape(b * h, skv, d)
    vr = jnp.repeat(v, rep, axis=1).reshape(b * h, skv, d)

    grid = (b * h, sq // bq)
    in_specs = [
        pl.BlockSpec((None, bq, d), lambda g, i: (g, i, 0)),
        pl.BlockSpec((None, skv, d), lambda g, i: (g, 0, 0)),
        pl.BlockSpec((None, skv, d), lambda g, i: (g, 0, 0)),
    ]
    args = [qr, kr, vr]
    if segmented:
        # one seg row per (batch, head) grid row, matching the q reshape
        args.append(jnp.repeat(jnp.asarray(q_segs, jnp.int32), h, axis=0))
        args.append(jnp.repeat(jnp.asarray(kv_segs, jnp.int32), h, axis=0))
        in_specs.append(pl.BlockSpec((None, bq), lambda g, i: (g, i)))
        in_specs.append(pl.BlockSpec((None, skv), lambda g, i: (g, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bkv=bkv, skv=skv, causal=causal,
                          window=int(window), softcap=float(softcap),
                          scale=scale, segmented=segmented),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, bq, d), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
    )(*args)
    return out.reshape(b, h, sq, d)
