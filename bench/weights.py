"""Weights from the seed, made on the device in the format they are served.

Users serve pre-quantized checkpoints, so a serving cell never runs the
grouping solver. The benchmark makes each weight matrix directly as MSB
4-bit block-64 codes with one 8-entry float32 codebook per 64-element block:
each weight draws a sign and one of the 8 levels with equal probability
(equal-count groups), and a block's codebook holds the conditional means of
the 8 equal-mass bins of a half-normal of the matrix's standard deviation,
scaled by a per-block log-normal factor. That is what the dynamic-grouping
solver produces, in expectation, for Gaussian weights.

Everything is a pure function of ``(seed, leaf name, layer)``, so the plain
reference regenerates one layer at a time, from the seed alone, the very
values the program was given. Norm gains are ``1 + delta`` with a small
random ``delta``; the program stores ``delta`` (its RMSNorm multiplies by
``1 + scale``).
"""
from __future__ import annotations

import math
import statistics
import zlib

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 64
LEVELS = 8
NORM_DELTA_STD = 0.05


def _level_means():
    """Means of the 8 equal-mass bins of a standard half-normal."""
    nd = statistics.NormalDist()
    edges = [nd.inv_cdf(0.5 + 0.5 * k / LEVELS) for k in range(LEVELS)]
    edges.append(math.inf)
    pdf = [math.exp(-x * x / 2) / math.sqrt(2 * math.pi) for x in edges]
    return np.array([2 * (pdf[k] - pdf[k + 1]) * LEVELS
                     for k in range(LEVELS)], np.float32)


LEVEL_MEANS = _level_means()


def seed_words(seed: int):
    """``seed`` as two uint32 device scalars, so one compiled generator
    serves every seed."""
    seed = int(seed) % (1 << 64)
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32(seed >> 32))


def key_from_words(lo, hi):
    key = jax.random.PRNGKey(lo)
    return jax.random.fold_in(key, hi)


def root_key(seed: int):
    """The PRNG key of any whole ``seed`` (taken modulo 2**64)."""
    return key_from_words(*seed_words(seed))


def leaf_key(root, name: str):
    return jax.random.fold_in(root, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def quant_matrix(key, shape, std):
    """Codes (int8, ``sign * (level + 1)``) and codebooks (float32,
    ``shape[:-1] + (shape[-1] // 64, 8)``) of one weight matrix; blocks run
    along the last axis."""
    *lead, n = shape
    if n % (2 * LEVELS) or n % BLOCK:
        raise ValueError(f"last dim {n} must be a multiple of {BLOCK}")
    k_bits, k_jit = jax.random.split(key)
    # one 32-bit draw gives eight 4-bit codes: sign bit + 3 level bits
    words = jax.random.bits(k_bits, (*lead, n // 8), jnp.uint32)
    shifts = jnp.arange(8, dtype=jnp.uint32) * 4
    nib = ((words[..., None] >> shifts) & 0xF).reshape(*lead, n)
    level = (nib & 0x7).astype(jnp.int8)
    sign = jnp.where((nib >> 3) > 0, jnp.int8(-1), jnp.int8(1))
    codes = sign * (level + 1)
    jitter = jnp.exp(0.1 * jax.random.normal(k_jit, (*lead, n // BLOCK)))
    scales = (std * jitter[..., None]
              * jnp.asarray(LEVEL_MEANS)).astype(jnp.float32)
    return codes, scales


def dequantize(codes, scales):
    """The plain decode of the codec, in float32: each weight is its sign
    times its block's codebook entry at its level (a select over the 8
    levels, which a TPU runs far faster than a gather)."""
    *lead, n = codes.shape
    lv = (jnp.abs(codes.astype(jnp.int32)) - 1).reshape(
        *lead, n // BLOCK, BLOCK)
    mag = jnp.zeros(lv.shape, jnp.float32)
    for k in range(LEVELS):
        mag = jnp.where(lv == k, scales[..., k:k + 1], mag)
    return jnp.sign(codes).astype(jnp.float32) * mag.reshape(*lead, n)


def norm_delta(key, shape):
    return (NORM_DELTA_STD * jax.random.normal(key, shape)).astype(
        jnp.bfloat16)


class DenseShapes:
    """Leaf names and one-layer shapes of a dense GQA SwiGLU decoder, from
    the configuration file's published numbers."""

    def __init__(self, arch: dict):
        d = arch["hidden_size"]
        h = arch["num_attention_heads"]
        kv = arch["num_key_value_heads"]
        hd = arch.get("head_dim") or d // h
        ff = arch["intermediate_size"]
        self.d, self.h, self.kv, self.hd, self.ff = d, h, kv, hd, ff
        self.n_layers = arch["num_hidden_layers"]
        self.vocab = arch["vocab_size"]
        self.vocab_padded = -(-self.vocab // 256) * 256
        self.tied = bool(arch["tie_word_embeddings"])
        self.std = float(arch.get("initializer_range", 0.02))
        self.layer_mats = {
            "attn/wq": (d, h * hd), "attn/wk": (d, kv * hd),
            "attn/wv": (d, kv * hd), "attn/wo": (h * hd, d),
            "mlp/wg": (d, ff), "mlp/wi": (d, ff), "mlp/wo": (ff, d)}
        self.layer_norms = ("norm1", "norm2")
        self.tables = ("embed",) if self.tied else ("embed", "unembed")

    def n_weights(self):
        per_layer = sum(a * b for a, b in self.layer_mats.values())
        return (self.n_layers * per_layer
                + len(self.tables) * self.vocab_padded * self.d)


def layer_weights(root, shapes: DenseShapes, layer):
    """One layer's codes, codebooks and norm deltas (``layer`` may be
    traced)."""
    out = {}
    for name, shape in shapes.layer_mats.items():
        k = jax.random.fold_in(leaf_key(root, name), layer)
        out[name] = quant_matrix(k, shape, shapes.std)
    for name in shapes.layer_norms:
        k = jax.random.fold_in(leaf_key(root, name), layer)
        out[name] = norm_delta(k, (shapes.d,))
    return out


def table_weights(root, shapes: DenseShapes, name):
    return quant_matrix(leaf_key(root, name),
                        (shapes.vocab_padded, shapes.d), shapes.std)


def final_norm(root, shapes: DenseShapes):
    return norm_delta(leaf_key(root, "final_norm"), (shapes.d,))


def program_tree(root, shapes: DenseShapes, dtype=jnp.bfloat16):
    """The program's parameter tree (``QTensor`` leaves for every matrix,
    decoded to ``dtype``, the model's compute type), layers stacked on a
    leading axis."""
    from repro.core.quantize import QTensor

    def q(codes_scales):
        codes, scales = codes_scales
        return QTensor(codes, scales, 4, BLOCK, dtype)

    layers = jax.vmap(lambda l: layer_weights(root, shapes, l))(
        jnp.arange(shapes.n_layers))
    block = {"norm1": layers["norm1"], "norm2": layers["norm2"],
             "attn": {k.split("/")[1]: q(v) for k, v in layers.items()
                      if k.startswith("attn/")},
             "mlp": {k.split("/")[1]: q(v) for k, v in layers.items()
                     if k.startswith("mlp/")}}
    tree = {"embed": q(table_weights(root, shapes, "embed")),
            "final_norm": final_norm(root, shapes),
            "dec": {"s0": block}}
    if not shapes.tied:
        tree["unembed"] = q(table_weights(root, shapes, "unembed"))
    return tree


def check_layout(tree, param_shapes):
    """Fail loudly where the program's parameter layout no longer matches
    the tree the benchmark builds."""
    from repro.core.quantize import QTensor
    got = jax.tree_util.tree_map(
        lambda x: tuple(x.shape), tree,
        is_leaf=lambda x: isinstance(x, QTensor))
    want = jax.tree_util.tree_map(lambda x: tuple(x.shape), param_shapes)
    if got != want:
        raise ValueError(f"parameter layout differs from the program's:\n"
                         f"bench   {got}\nprogram {want}")


def packed_params(seed: int, shapes: DenseShapes, param_shapes,
                  dtype=jnp.bfloat16):
    """Every weight, made on the device from ``seed`` and packed by the
    program's own ``pack_params``, in one jitted call. ``param_shapes`` is
    the program's own account of its layout, checked first."""
    from repro.core.policy import pack_params
    check_layout(jax.eval_shape(lambda: program_tree(root_key(0), shapes)),
                 param_shapes)
    build = jax.jit(lambda lo, hi: pack_params(
        program_tree(key_from_words(lo, hi), shapes, dtype))[0])
    return build(*seed_words(seed))
