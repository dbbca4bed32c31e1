"""GPipe-style pipeline parallelism over a dedicated `stage` mesh axis.

Not required by the assigned meshes (2-axis pods), but the scan-over-layers
structure makes staging natural: the layer stack splits into
``n_stages`` contiguous groups, microbatches flow stage-to-stage via
``ppermute`` inside a ``shard_map``, and every stage computes each tick
(classic GPipe fill/drain bubble = (S-1)/(M+S-1)).

``pipeline_forward`` is the schedule skeleton; tests/test_pipeline.py
verifies it equals sequential layer application on a host mesh. Wiring it
under ``Model`` means adding a "stage" axis to ``make_production_mesh`` and
stacking params (n_stages, layers_per_stage, ...) — the param layout
already supports an extra leading dim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from jax import shard_map


def pipeline_forward(layer_fn, params, x_micro, mesh, stage_axis="stage"):
    """Run microbatches through pipeline stages.

    layer_fn(stage_params, x) -> x : applies one stage's layer group
    params: pytree, leaves (n_stages, ...) — sharded over `stage`
    x_micro: (n_micro, mb, ...) microbatched input (replicated)
    Returns (n_micro, mb, ...) outputs after all stages.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = x_micro.shape[0]
    n_ticks = n_micro + n_stages - 1
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def stage_prog(params_local, xs):
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_local)
        stage = jax.lax.axis_index(stage_axis)
        zero = jnp.zeros_like(xs[0])

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (while available)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(t < n_micro, xs[mb_idx], zero)
            x_in = jnp.where(stage == 0, inject, buf)
            y = layer_fn(params_local, x_in)
            # last stage emits microbatch (t - (n_stages - 1)) at this tick
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            emit = (stage == n_stages - 1) & (t >= n_stages - 1)
            outs = jnp.where(
                emit,
                jax.lax.dynamic_update_index_in_dim(outs, y, out_idx, 0),
                outs)
            buf_next = jax.lax.ppermute(y, stage_axis, fwd_perm)
            return (buf_next, outs), None

        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(tick, (zero, outs0),
                                    jnp.arange(n_ticks))
        # only the last stage holds real outputs; broadcast via masked psum
        outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, stage_axis)

    return shard_map(
        stage_prog, mesh=mesh,
        in_specs=(P(stage_axis), P()), out_specs=P(),
        check_vma=False)(params, x_micro)
