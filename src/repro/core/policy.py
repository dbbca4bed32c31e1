"""Quantization policy: apply MSB PTQ to a model parameter pytree.

``QuantPolicy`` is the framework-level integration point of the paper's
technique: it selects which parameter leaves get quantized (by path regex +
rank/size thresholds), at what bits/granularity/solver, and rewrites the
params pytree in place with ``QTensor`` leaves. Layer stacks produced by
scan-over-layers (leading layer dim) are handled by folding the layer dim
into the block batch — blocks never straddle rows, so the grouping is
identical to quantizing each layer separately.

Quantization is *local to each weight shard* on a mesh (no collectives); see
examples/distributed_quantize.py.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import jax
import jax.numpy as jnp

from .quantize import (PackedQTensor, QTensor, dequantize, pack_qtensor,
                       quantize_blockwise, quantize_pertensor, tp_pad_packed_k,
                       tp_pad_packed_n, tp_pad_q_k, tp_pad_q_n)


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    bits: int = 4
    block: int = 64              # -1 => per-tensor
    solver: str = "dp"           # dp | kmeans | wgm | gg | wgm_lo (CPU)
    lam: float = 0.0
    include: str = r".*"
    exclude: str = (r".*(norm|scale_param|bias|ln|rope|router|conv_w|"
                    r"dt_bias|a_log|d_skip|f_bias|w_rec).*")
    min_size: int = 1 << 12      # skip tiny leaves (norm scales etc.)
    double_quant: bool = False

    def selects(self, path: str, leaf) -> bool:
        if not hasattr(leaf, "ndim") or leaf.ndim < 2 or leaf.size < self.min_size:
            return False
        if re.match(self.exclude, path, re.I):
            return False
        if self.block != -1 and leaf.shape[-1] % abs(self.block):
            return False
        return re.match(self.include, path, re.I) is not None


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
    return "/".join(parts)


def quantize_params(params, policy: QuantPolicy = QuantPolicy(), verbose=False):
    """Rewrite matching leaves of ``params`` as QTensor. Returns (tree, report)."""
    report = {}

    def visit(path, leaf):
        p = _path_str(path)
        if isinstance(leaf, QTensor) or not policy.selects(p, leaf):
            return leaf
        if policy.block == -1:
            solver = "wdp" if policy.solver == "dp" else policy.solver
            if leaf.ndim >= 3:
                # stacked (scan-over-layers) params: per-tensor = per layer
                # matrix; vmap keeps the leading layer dim scannable
                q = jax.vmap(lambda w: quantize_pertensor(
                    w, bits=policy.bits, solver=solver, lam=policy.lam))(leaf)
            else:
                q = quantize_pertensor(leaf, bits=policy.bits, solver=solver,
                                       lam=policy.lam)
        else:
            q = quantize_blockwise(leaf, bits=policy.bits, block=policy.block,
                                   solver=policy.solver, lam=policy.lam)
        if policy.double_quant:
            from .quantize import double_quantize
            q = double_quantize(q)
        report[p] = (leaf.shape, policy.bits)
        if verbose:
            print(f"  quantized {p}: {leaf.shape} -> {policy.bits}b/"
                  f"{'tensor' if policy.block == -1 else policy.block}")
        return q

    tree = jax.tree_util.tree_map_with_path(visit, params)
    return tree, report


def pack_params(params, verbose=False):
    """QTensor leaves -> PackedQTensor (packed execution; DESIGN.md Sec. 9).

    The one-time load pass behind ``execution="packed"``: every 4-bit
    block-64 ``QTensor`` becomes a kernel-layout ``PackedQTensor`` so no
    forward ever re-packs. The ``unembed`` table packs *transposed*
    (k-blocked scales) so the unembedding projection runs through the fused
    kernel; ``embed`` packs in natural orientation for the row-gather path.
    Leaves the pass cannot pack (per-tensor QTensors, other bit-widths,
    plain arrays) stay as-is and keep their simulation-mode execution.
    Returns (tree, report).

    Contract: packing is value-preserving — ``dequantize_params`` of the
    packed tree equals the unpacked tree's dequantization exactly, except
    that stored exact-zero codes re-emerge as ``+alpha_0`` (the packed
    format trades the zero special-case for density; DESIGN.md Sec. 7).
    The input tree is not mutated. Run this *before*
    ``tp_partition_params`` — the TP planner consumes packed layouts.
    """
    report = {}

    def visit(path, leaf):
        if not isinstance(leaf, QTensor):
            return leaf
        p = _path_str(path)
        if leaf.bits != 4 or leaf.block != 64:
            return leaf                      # no packed layout — simulate
        transpose = p == "unembed" and leaf.codes.ndim == 2
        pq = pack_qtensor(leaf, transpose=transpose)
        report[p] = (leaf.shape, "kblocked" if transpose else "nblocked")
        if verbose:
            print(f"  packed {p}: {leaf.shape} -> "
                  f"{'transposed/kblocked' if transpose else 'nblocked'} "
                  f"uint8 {pq.packed.shape}")
        return pq

    tree = jax.tree_util.tree_map_with_path(
        visit, params, is_leaf=lambda x: isinstance(x, QTensor))
    return tree, report


# ---------------------------------------------------------------------------
# Tensor-parallel partitioning (DESIGN.md Sec. 10)
# ---------------------------------------------------------------------------

def _is_q(x):
    return isinstance(x, (QTensor, PackedQTensor))


def _storage_n(leaf):
    """Stored output-dim width (incl. pack padding)."""
    return leaf.n_pad if isinstance(leaf, PackedQTensor) else leaf.codes.shape[-1]


def _storage_k(leaf):
    a = leaf.packed if isinstance(leaf, PackedQTensor) else leaf.codes
    return a.shape[-2]


def _roundup(x, m):
    return -(-x // m) * m


def _axis_spec(ndim, pos, axis):
    from jax.sharding import PartitionSpec as P
    parts = [None] * ndim
    parts[pos] = axis
    return P(*parts)


def _leaf_spec(leaf, kind, axis):
    """PartitionSpec subtree matching one (marked) leaf's pytree structure.

    For Q/Packed leaves the returned object is the *same dataclass* with its
    array fields replaced by PartitionSpecs, so a spec tree built this way
    flattens leaf-for-leaf against the params tree (shard_map in_specs,
    device_put shardings).
    """
    from jax.sharding import PartitionSpec as P
    if isinstance(leaf, PackedQTensor):
        # scales (..., N/64, 8, K) n-blocked, (..., K/64, 8, N) k-blocked
        pd, sd = leaf.packed.ndim, leaf.scales.ndim
        dims = {None: None,
                "n": (pd - 1, sd - 1 if leaf.kblocked else sd - 3),
                "k": (pd - 2, sd - 1), "e": (pd - 3, sd - 4)}[kind]
        if dims is None:
            return dataclasses.replace(leaf, packed=P(), scales=P())
        return dataclasses.replace(leaf, packed=_axis_spec(pd, dims[0], axis),
                                   scales=_axis_spec(sd, dims[1], axis))
    if isinstance(leaf, QTensor):
        cd, sd = leaf.codes.ndim, leaf.scales.ndim
        dims = {None: None, "n": (cd - 1, sd - 2), "k": (cd - 2, sd - 3),
                "v": (cd - 2, sd - 3), "e": (cd - 3, sd - 4)}[kind]
        if dims is None:
            return dataclasses.replace(leaf, codes=P(), scales=P())
        return dataclasses.replace(leaf, codes=_axis_spec(cd, dims[0], axis),
                                   scales=_axis_spec(sd, dims[1], axis))
    if kind == "n" and hasattr(leaf, "ndim"):          # column-parallel bias
        return _axis_spec(leaf.ndim, leaf.ndim - 1, axis)
    return P()


def _n_shardable_exact(leaf, tp):
    """Can this leaf's output dim split into whole per-rank blocks with NO
    padding (required for head-sharded attention projections)?"""
    if isinstance(leaf, PackedQTensor):
        return (not leaf.kblocked and leaf.n == leaf.n_pad
                and leaf.n_pad % (64 * tp) == 0)
    if isinstance(leaf, QTensor):
        return leaf.block == 64 and leaf.codes.shape[-1] % (64 * tp) == 0
    return False


def tp_partition_params(params, tp_size, cfg=None, axis="model",
                        verbose=False):
    """Partition a (quantized/packed) params tree for tensor parallelism.

    The Megatron-style plan, applied to MSB storage (DESIGN.md Sec. 10):

      * attention ``wq/wk/wv`` (+ qkv biases) column-parallel along heads,
        ``wo`` row-parallel with a psum — only when every projection is
        quantized and ``n_heads``/``n_kv_heads`` divide ``tp_size`` with
        64-block-aligned per-rank widths (no padding is ever introduced
        inside a head); otherwise the whole attention layer replicates and
        the engines fall back to slicing *computed* heads for the paged
        cache.
      * MLP ``wg/wi`` column-parallel, ``wo`` row-parallel. The shared
        hidden width is padded to a multiple of ``64*tp_size`` with
        exact-zero columns/rows, so any ``d_ff`` shards.
      * MoE expert tensors shard along the expert dim when it divides
        ``tp_size`` (each rank runs its resident experts; the combine is a
        psum). The router replicates.
      * ``unembed`` shards along vocab (column-parallel logits + an
        all_gather); ``embed`` replicates (it also backs the tied-embedding
        logits path and the row-gather embedding lookup).
      * Everything else (norms, plain arrays, per-tensor QTensors)
        replicates.

    Marks each sharded leaf's ``shard`` aux so the model code knows where
    to psum/all_gather, and returns ``(params, specs, report)`` where
    ``specs`` is a PartitionSpec pytree flattening leaf-for-leaf against
    ``params`` (feed to ``shard_map`` in_specs / ``device_put``) and
    ``report`` maps group paths to what was done. ``tp_size == 1`` returns
    the tree unchanged with fully replicated specs.
    """
    from jax.sharding import PartitionSpec as P
    report = {}
    tp = int(tp_size)

    def repl(node):
        if isinstance(node, dict):
            return {k: repl(v) for k, v in node.items()}
        return _leaf_spec(node, None, None)

    def mark(leaf, kind):
        return dataclasses.replace(leaf, shard=kind) if _is_q(leaf) else leaf

    def attn_group(group, path):
        names = ("wq", "wk", "wv", "wo")
        ok = (tp > 1 and cfg is not None and not cfg.is_encdec
              and cfg.n_heads % tp == 0 and cfg.n_kv_heads % tp == 0
              and all(_is_q(group.get(nm)) for nm in names)
              and all(_n_shardable_exact(group[nm], tp)
                      for nm in ("wq", "wk", "wv"))
              and _storage_k(group["wo"]) % tp == 0)
        if not ok:
            return dict(group), repl(group)
        new, spec = {}, {}
        for key, leaf in group.items():
            kind = {"wq": "n", "wk": "n", "wv": "n", "wo": "k",
                    "bq": "n", "bk": "n", "bv": "n"}.get(key)
            new[key] = mark(leaf, kind)
            spec[key] = _leaf_spec(new[key], kind, axis)
        report[path] = "heads"
        return new, spec

    def mlp_group(group, path):
        ws = ("wg", "wi", "wo")
        if tp <= 1 or not all(_is_q(group.get(w)) for w in ws) or any(
                isinstance(group[w], QTensor) and group[w].block != 64
                for w in ws):
            return dict(group), repl(group)
        f_to = _roundup(max(_storage_n(group["wg"]), _storage_n(group["wi"])),
                        64 * tp)
        new, spec = {}, {}
        for key, leaf in group.items():
            if key in ("wg", "wi"):
                leaf = (tp_pad_packed_n(leaf, f_to)
                        if isinstance(leaf, PackedQTensor)
                        else tp_pad_q_n(leaf, f_to))
                kind = "n"
            elif key == "wo":
                leaf = (tp_pad_packed_k(leaf, f_to)
                        if isinstance(leaf, PackedQTensor)
                        else tp_pad_q_k(leaf, f_to))
                kind = "k"
            else:
                kind = None
            new[key] = mark(leaf, kind)
            spec[key] = _leaf_spec(new[key], kind, axis)
        report[path] = f"column/row hidden->{f_to}"
        return new, spec

    def moe_group(group, path):
        ws = ("wg", "wi", "wo")
        ok = (tp > 1 and all(_is_q(group.get(w)) for w in ws)
              and all(group[w].codes.ndim >= 3 if isinstance(group[w], QTensor)
                      else group[w].packed.ndim >= 3 for w in ws))
        if ok:
            e = (group["wg"].packed.shape[-3]
                 if isinstance(group["wg"], PackedQTensor)
                 else group["wg"].codes.shape[-3])
            ok = e % tp == 0
        if not ok:
            return dict(group), repl(group)
        new, spec = {}, {}
        for key, leaf in group.items():
            kind = "e" if key in ws else None
            new[key] = mark(leaf, kind)
            spec[key] = _leaf_spec(new[key], kind, axis)
        report[path] = "experts"
        return new, spec

    def unembed_leaf(leaf, path):
        if tp <= 1 or not _is_q(leaf):
            return leaf, _leaf_spec(leaf, None, None)
        if isinstance(leaf, PackedQTensor):
            if not leaf.kblocked:
                return leaf, _leaf_spec(leaf, None, None)
            # whole 256-column tiles per rank: the kernel's k-blocked
            # column tile (kernels/msb_matmul pick_blocks)
            v_to = _roundup(leaf.n_pad, 256 * tp)
            leaf = tp_pad_packed_n(leaf, v_to)
            kind = "n"
        else:
            if leaf.block == -1:
                return leaf, _leaf_spec(leaf, None, None)
            v_to = _roundup(leaf.codes.shape[-2], tp)
            leaf = tp_pad_q_k(leaf, v_to)
            kind = "v"
        report[path] = f"vocab->{v_to}"
        leaf = mark(leaf, kind)
        return leaf, _leaf_spec(leaf, kind, axis)

    def walk(node, path):
        if not isinstance(node, dict):
            return node, repl(node)
        new, spec = {}, {}
        for key, val in node.items():
            p = f"{path}/{key}" if path else key
            if key in ("attn", "xattn") and isinstance(val, dict):
                new[key], spec[key] = attn_group(val, p)
            elif key == "mlp" and isinstance(val, dict):
                new[key], spec[key] = mlp_group(val, p)
            elif key == "moe" and isinstance(val, dict):
                new[key], spec[key] = moe_group(val, p)
            elif key == "unembed":
                new[key], spec[key] = unembed_leaf(val, p)
            else:
                new[key], spec[key] = walk(val, p)
        return new, spec

    new_params, specs = walk(params, "")
    if verbose:
        for p, what in sorted(report.items()):
            print(f"  tp-sharded {p}: {what}")
    return new_params, specs, report


def tp_localize(params):
    """Rebind each n-sharded PackedQTensor's static ``n`` to its local width.

    Inside ``shard_map`` the array leaves are per-rank slices but the pytree
    aux still carries the *global* padded width; run this on the local tree
    before any matmul so ``packed_matmul`` does not slice past the shard.
    K-, expert- and vocab-sharded leaves keep their aux unchanged (their
    ``n``/logical width is not the sharded dim). No-op outside shard_map.
    """
    def fix(leaf):
        if isinstance(leaf, PackedQTensor) and leaf.shard == "n":
            return dataclasses.replace(leaf, n=leaf.packed.shape[-1] * 2)
        return leaf

    return jax.tree_util.tree_map(
        fix, params, is_leaf=lambda x: isinstance(x, PackedQTensor))


def dequantize_params(params, dtype=None):
    """Materialize all quantized leaves back to dense arrays (simulation mode).

    PackedQTensor leaves come back in their *original* orientation (a
    transposed unembedding pack is transposed back to ``(V, D)``)."""
    def visit(leaf):
        if isinstance(leaf, PackedQTensor):
            w = leaf.dequantize()
            if leaf.kblocked:
                w = w.T
        elif isinstance(leaf, QTensor):
            w = dequantize(leaf)
        else:
            return leaf
        return w.astype(dtype) if dtype is not None else w
    return jax.tree_util.tree_map(
        visit, params,
        is_leaf=lambda x: isinstance(x, (QTensor, PackedQTensor)))


def param_bits(params):
    """Total storage bits of a (possibly mixed) params tree.

    PackedQTensor leaves report their real allocated footprint: uint8
    packed codes (8 bits/byte) + the scale table, N-padding included."""
    total = 0

    def visit(leaf):
        nonlocal total
        if isinstance(leaf, PackedQTensor):
            scale_bits = jnp.dtype(leaf.scales.dtype).itemsize * 8
            total += leaf.packed.size * 8 + leaf.scales.size * scale_bits
        elif isinstance(leaf, QTensor):
            scale_bits = jnp.dtype(leaf.scales.dtype).itemsize * 8
            total += leaf.codes.size * leaf.bits + leaf.scales.size * scale_bits
        elif hasattr(leaf, "size"):
            total += leaf.size * jnp.dtype(leaf.dtype).itemsize * 8
        return leaf

    jax.tree_util.tree_map(visit, params,
                           is_leaf=lambda x: isinstance(x, (QTensor,
                                                            PackedQTensor)))
    return total
