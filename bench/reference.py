"""Plain float32 reference forward of a dense GQA SwiGLU decoder.

Written from the published description (InternLM2, Granite 3.0; both are
Llama-style decoders) in straightforward ``jax.numpy``, with no kernel,
cache or batching of the program's. It imports nothing of the program and
takes nothing the program made: each layer's weights are regenerated from
the seed by ``weights.py`` and decoded here.

    x = embed[tokens] * embedding_multiplier
    per layer:  h = rmsnorm(x) * g1
                x = x + attention(rope(h Wq), rope(h Wk), h Wv) Wo
                h = rmsnorm(x) * g2
                x = x + (silu(h Wg) * (h Wi)) Wo2
    logits = (rmsnorm(x) * gf) U^T         (U = embed when tied)

Attention is causal with grouped KV heads (head i reads KV head
i // (heads / kv_heads)), rotary embeddings rotate the two halves of each
head, and the scores are scaled by ``attention_multiplier`` where the
configuration gives one, else by head_dim ** -0.5.

Departures, noted in the configuration files: Granite's
``residual_multiplier`` and ``logits_scaling`` are left out, as in the
program, which cannot express them.

``dot_dtype`` runs every matrix product on inputs rounded to that type with
float32 accumulation: float32 at ``highest`` precision is the reference;
a float8 type makes the lower-precision control.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(dot_dtype, spec, a, b):
    if dot_dtype == jnp.float32:
        return jnp.einsum(spec, a, b, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
    # inputs rounded to dot_dtype; a float8 value is exact in bfloat16, so
    # the bfloat16 product with float32 accumulation is the float8 product
    def rnd(x):
        return x.astype(dot_dtype).astype(jnp.bfloat16)
    return jnp.einsum(spec, rnd(a), rnd(b),
                      preferred_element_type=jnp.float32)


def rms_norm(x, delta, eps):
    gain = 1.0 + delta.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, positions, theta):
    """x (L, heads, hd), positions (L,): rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


class Reference:
    """The reference for one configuration file's published numbers."""

    def __init__(self, arch: dict, dot_dtype=jnp.float32):
        self.arch = arch
        self.s = W.DenseShapes(arch)
        self.eps = float(arch["rms_norm_eps"])
        self.theta = float(arch["rope_theta"])
        self.embed_mult = float(arch.get("embedding_multiplier", 1.0))
        self.q_scale = float(arch.get("attention_multiplier",
                                      self.s.hd ** -0.5))
        self.dot = dot_dtype

    # -- pieces, each one jitted program over a (S, L) block of sequences --
    @functools.partial(jax.jit, static_argnums=0)
    def embed(self, lo, hi, tokens):
        root = W.key_from_words(lo, hi)
        codes, scales = W.table_weights(root, self.s, "embed")
        table = W.dequantize(codes, scales)
        return jnp.take(table, tokens, axis=0) * self.embed_mult

    @functools.partial(jax.jit, static_argnums=0)
    def layer(self, lo, hi, layer, x):
        s, mm = self.s, functools.partial(_mm, self.dot)
        lw = W.layer_weights(W.key_from_words(lo, hi), s, layer)
        w = {k: W.dequantize(*v) for k, v in lw.items()
             if k not in s.layer_norms}
        rep = s.h // s.kv
        pos = jnp.arange(x.shape[1])
        causal = pos[None, :] <= pos[:, None]

        def attend(xs):                                   # one sequence
            h = rms_norm(xs, lw["norm1"], self.eps)
            q = mm("ld,df->lf", h, w["attn/wq"]).reshape(-1, s.h, s.hd)
            k = mm("ld,df->lf", h, w["attn/wk"]).reshape(-1, s.kv, s.hd)
            v = mm("ld,df->lf", h, w["attn/wv"]).reshape(-1, s.kv, s.hd)
            q = rope(q, pos, self.theta).reshape(-1, s.kv, rep, s.hd)
            k = rope(k, pos, self.theta)
            sc = mm("qkrd,skd->krqs", q, k) * self.q_scale
            sc = jnp.where(causal[None, None], sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            o = mm("krqs,skd->qkrd", p, v).reshape(-1, s.h * s.hd)
            xs = xs + mm("lf,fd->ld", o, w["attn/wo"])
            h = rms_norm(xs, lw["norm2"], self.eps)
            g = jax.nn.silu(mm("ld,df->lf", h, w["mlp/wg"]))
            u = mm("ld,df->lf", h, w["mlp/wi"])
            return xs + mm("lf,fd->ld", g * u, w["mlp/wo"])

        return jax.lax.map(attend, x)

    @functools.partial(jax.jit, static_argnums=0)
    def head(self, lo, hi, x, picks):
        """Per position: the best logit, the logit of ``picks`` (the token
        to judge there), and this forward's own first choice."""
        root = W.key_from_words(lo, hi)
        codes, scales = W.table_weights(
            root, self.s, "embed" if self.s.tied else "unembed")
        table = W.dequantize(codes, scales)[: self.s.vocab]
        delta = W.final_norm(root, self.s)

        def one(args):
            xs, pk = args
            h = rms_norm(xs, delta, self.eps)
            logits = _mm(self.dot, "ld,vd->lv", h, table)
            best = jnp.max(logits, -1)
            got = jnp.take_along_axis(logits, pk[:, None], -1)[:, 0]
            return best, got, jnp.argmax(logits, -1).astype(jnp.int32)

        return jax.lax.map(one, (x, picks))

    @functools.partial(jax.jit, static_argnums=0)
    def logits(self, lo, hi, x):
        """Whole logits over the real vocabulary (small sizes only)."""
        root = W.key_from_words(lo, hi)
        codes, scales = W.table_weights(
            root, self.s, "embed" if self.s.tied else "unembed")
        table = W.dequantize(codes, scales)[: self.s.vocab]
        h = rms_norm(x, W.final_norm(root, self.s), self.eps)
        return _mm(self.dot, "sld,vd->slv", h, table)

    def hidden(self, seed: int, tokens):
        """The last layer's output for (S, L) tokens."""
        lo, hi = W.seed_words(seed)
        x = self.embed(lo, hi, jnp.asarray(tokens, jnp.int32))
        for layer in range(self.s.n_layers):
            x = self.layer(lo, hi, jnp.int32(layer), x)
        return x

    def forward(self, seed: int, tokens, picks):
        """tokens, picks: (S, L) int32. Returns numpy (best, picked logit,
        argmax), each (S, L), computed layer by layer."""
        lo, hi = W.seed_words(seed)
        t0 = time.monotonic()
        x = jax.block_until_ready(self.hidden(seed, tokens))
        t1 = time.monotonic()
        out = self.head(lo, hi, x, jnp.asarray(picks, jnp.int32))
        out = tuple(np.asarray(a) for a in out)
        self.seconds = {"layers": t1 - t0, "head": time.monotonic() - t1}
        return out


def pack_sequences(seqs, length):
    """Token lists -> (S, length) int32, zero padded on the right."""
    out = np.zeros((len(seqs), length), np.int32)
    for i, t in enumerate(seqs):
        out[i, : len(t)] = t
    return out


def served_gaps(ref: Reference, seed, prompts, served, length):
    """Widest gap, per request, by which a served token's reference logit
    lies below the reference's best at its position.

    prompts[i] and served[i] are token lists; the forward sees
    prompt + served[:-1], and served token j is judged at position
    len(prompt) + j - 1. Returns (gaps per request, total tokens judged).
    """
    seqs = [list(p) + list(s[:-1]) for p, s in zip(prompts, served)]
    picks = np.zeros((len(seqs), length), np.int32)
    for i, (p, s) in enumerate(zip(prompts, served)):
        picks[i, len(p) - 1: len(p) - 1 + len(s)] = s
    best, got, _ = ref.forward(seed, pack_sequences(seqs, length), picks)
    gaps = []
    for i, (p, s) in enumerate(zip(prompts, served)):
        sl = slice(len(p) - 1, len(p) - 1 + len(s))
        gaps.append(float(np.max(best[i, sl] - got[i, sl])))
    return gaps, sum(len(s) for s in served)


def control_gaps(ref: Reference, ctl: Reference, seed, prompts, served,
                 length):
    """The control's reading: at each position where a token was served,
    the gap, under ``ref``, of the token that ``ctl`` (the reference in a
    lower precision) puts first, teacher-forced on the same tokens."""
    seqs = [list(p) + list(s[:-1]) for p, s in zip(prompts, served)]
    tokens = pack_sequences(seqs, length)
    _, _, first = ctl.forward(seed, tokens, np.zeros_like(tokens))
    best, got, _ = ref.forward(seed, tokens, first)
    gaps = []
    for i, (p, s) in enumerate(zip(prompts, served)):
        sl = slice(len(p) - 1, len(p) - 1 + len(s))
        gaps.append(float(np.max(best[i, sl] - got[i, sl])))
    return gaps
