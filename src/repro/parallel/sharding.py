"""Sharding policy: logical axis names -> mesh PartitionSpecs.

Every param/cache/activation leaf carries a tuple of logical axis names; the
resolver maps them to mesh axes with two production rules:
  1. divisibility — an axis is only assigned if the dim divides by the mesh
     axis size (uneven shardings are rejected by jax.jit on inputs);
  2. exclusivity — each mesh axis is used at most once per leaf, in dim
     order, so fallback names ("kv_seq" after "batch") pick up idle axes
     (e.g. long_500k batch=1 -> the KV-cache sequence dim takes `data`).

Logical vocabulary:
  fsdp/embed       -> data            (ZeRO-3 weight shard)
  tp/mlp/heads/kv/vocab/head_dim/experts -> model  (tensor/expert parallel)
  batch, kv_seq    -> (pod, data)     (data parallel; seq as fallback)
  layers/None      -> replicated
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class TPShard:
    """Manual tensor-parallel context, live only *inside* ``shard_map``.

    Where ``ParallelContext`` drives GSPMD (sharding constraints on global
    arrays, the compiler inserts collectives), ``TPShard`` drives the
    *manual* serving data plane: the engines split params/caches per rank
    with ``shard_map`` and the model code issues its own collectives —
    ``psum`` after row-parallel (K-sharded) matmuls, ``all_gather`` over
    heads or vocab shards. Model functions distinguish the two by type:
    a ``TPShard`` ``parallel=`` argument means "you are running on the
    local shard of a mesh axis named ``axis`` of size ``size``".

    Multi-iteration bodies compose: the decode-horizon scan
    (``Model.paged_decode_horizon``, DESIGN.md Sec. 12) runs *inside* the
    ``shard_map`` region, so H fused decode iterations — per-iteration
    psums, logit all_gathers and on-device argmax included — are still one
    dispatch per mesh, with sampling replicated across ranks because every
    collective completes before the argmax reads the logits.
    """
    axis: str = "model"
    size: int = 1


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    mesh: Mesh
    dp_axes: Tuple[str, ...]
    tp_axis: str = "model"
    fsdp_axis: Optional[str] = "data"

    @property
    def dp_size(self):
        return math.prod(self.mesh.shape[a] for a in self.dp_axes)

    @property
    def tp_size(self):
        return self.mesh.shape[self.tp_axis]

    @property
    def n_devices(self):
        return math.prod(self.mesh.shape.values())

    def logical_map(self):
        """logical name -> candidate mesh-axis tuples, tried in order."""
        fsdp = [(self.fsdp_axis,)] if self.fsdp_axis else [()]
        tp = [(self.tp_axis,)]
        dp = tuple(self.dp_axes)
        return {
            "fsdp": fsdp, "embed": fsdp,
            "tp": tp, "mlp": tp, "heads": tp, "kv": tp, "vocab": tp,
            "head_dim": tp, "experts": tp, "sp": tp,
            "batch": [dp],
            # KV-cache sequence: grab every idle axis (long_500k batch=1 ->
            # all 512 ways), else whatever dp/tp remains free
            "kv_seq": [dp + (self.tp_axis,), dp, (self.tp_axis,)],
            "layers": [()], None: [()],
        }


def from_mesh(mesh: Mesh) -> ParallelContext:
    names = mesh.axis_names
    dp = tuple(a for a in names if a in ("pod", "data"))
    # a pure tensor-parallel mesh (("model",) only — the serving engines'
    # default) has no data axis at all: dp_size == 1
    if not dp and "model" in names and len(names) == 1:
        dp = ()
    elif not dp:
        dp = (names[0],)
    return ParallelContext(mesh=mesh, dp_axes=dp,
                           tp_axis="model" if "model" in names else names[-1],
                           fsdp_axis="data" if "data" in names else None)


def resolve_spec(axes, shape, ctx: ParallelContext) -> P:
    """Map one leaf's logical axes to a PartitionSpec (see module doc)."""
    lm = ctx.logical_map()
    used = set()
    parts = []
    for dim, name in zip(shape, axes):
        cands = lm.get(name, [()])
        if isinstance(cands, tuple):
            cands = [cands]
        chosen = None
        for mesh_axes in cands:
            size = (math.prod(ctx.mesh.shape[a] for a in mesh_axes)
                    if mesh_axes else 1)
            if (mesh_axes and not (set(mesh_axes) & used)
                    and size > 1 and dim % size == 0):
                chosen = mesh_axes
                break
        if chosen:
            parts.append(chosen if len(chosen) > 1 else chosen[0])
            used.update(chosen)
        else:
            parts.append(None)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_shardings(ctx: ParallelContext, axes_tree, shape_tree):
    """NamedSharding pytree for (axes, shapes) trees of identical structure."""
    def one(axes, shp):
        shape = shp.shape if hasattr(shp, "shape") else shp
        return NamedSharding(ctx.mesh, resolve_spec(axes, shape, ctx))
    return jax.tree_util.tree_map(
        one, axes_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))


def constraint(x, axes, ctx: Optional[ParallelContext]):
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, resolve_spec(axes, x.shape, ctx)))
