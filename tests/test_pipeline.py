"""Pipeline-parallel schedule == sequential layer application."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.parallel.pipeline import pipeline_forward


def test_pipeline_matches_sequential(rng):
    n_stages, n_micro, mb, d = 4, 6, 2, 8
    mesh = jax.make_mesh((n_stages,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    ws = jnp.asarray(rng.standard_normal((n_stages, d, d)) * 0.3, jnp.float32)
    xs = jnp.asarray(rng.standard_normal((n_micro, mb, d)), jnp.float32)

    def layer_fn(w, x):
        return jnp.tanh(x @ w)

    ws_sharded = jax.device_put(ws, NamedSharding(mesh, P("stage")))
    with mesh:
        out = jax.jit(lambda w, x: pipeline_forward(
            layer_fn, w, x, mesh))(ws_sharded, xs)

    ref = xs
    for s in range(n_stages):
        ref = jax.vmap(lambda x: layer_fn(ws[s], x))(ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
