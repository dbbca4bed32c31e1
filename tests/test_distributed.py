"""Distributed semantics on 8 host devices: sharded == single-device."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import smoke_config
from repro.models import Model
from repro.models.moe import moe_layer
from repro.parallel import ParallelContext, from_mesh, resolve_spec, \
    tree_shardings
from repro.train import AdamW, OptConfig, init_state, make_train_step


def make_mesh(shape=(4, 2), axes=("data", "model")):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def test_resolver_rules():
    ctx = from_mesh(make_mesh())
    # divisible dims get sharded
    assert resolve_spec(("fsdp", "tp"), (8, 16), ctx) == P("data", "model")
    # non-divisible dims are dropped
    assert resolve_spec(("fsdp", "tp"), (3, 16), ctx) == P(None, "model")
    # kv_seq grabs every idle axis: tp when batch took data, everything
    # (joint) when batch is unshardable (long_500k), data+pod leftovers
    assert resolve_spec(("batch", "kv_seq"), (8, 64), ctx) == P("data", "model")
    assert resolve_spec(("batch", "kv_seq"), (1, 64), ctx) == \
        P(None, ("data", "model"))
    assert resolve_spec(("batch", "kv_seq"), (8, 3), ctx) == P("data",)
    # heads fallback to head_dim
    assert resolve_spec(("batch", None, "heads", "head_dim"),
                        (8, 4, 3, 16), ctx) == P("data", None, None, "model")


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "granite-moe-3b-a800m",
                                  "jamba-v0.1-52b"])
def test_sharded_loss_matches_single_device(arch):
    """The distributed forward is numerically the single-device forward."""
    cfg = smoke_config(arch)
    cfg = dataclasses.replace(cfg, n_experts=8) if cfg.n_experts else cfg
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S = 8, 32
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)}
    batch["labels"] = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)

    loss_1, _ = jax.jit(model.loss)(params, batch)

    mesh = make_mesh()
    ctx = from_mesh(mesh)
    psh = tree_shardings(ctx, model.param_axes(), model.param_shapes())
    params_s = jax.device_put(params, psh)
    batch_s = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    with mesh:
        loss_8, _ = jax.jit(
            lambda p, b: model.loss(p, b, ctx))(params_s, batch_s)
    # MoE capacity differs per shard layout -> small tolerance for moe archs
    tol = 0.05 if cfg.n_experts else 1e-3
    assert float(loss_8) == pytest.approx(float(loss_1), rel=tol)


def test_moe_ep_matches_local(rng):
    """shard_map all-to-all EP == single-device dispatch (same capacity)."""
    cfg = dataclasses.replace(smoke_config("granite-moe-3b-a800m"),
                              n_experts=8, n_experts_active=2,
                              capacity_factor=8.0)   # no drops -> exact
    d, e, f = cfg.d_model, cfg.n_experts_padded, cfg.d_ff
    p = {"router": jnp.asarray(rng.standard_normal((d, e)), jnp.float32),
         "wg": jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.float32),
         "wi": jnp.asarray(rng.standard_normal((e, d, f)) * 0.05, jnp.float32),
         "wo": jnp.asarray(rng.standard_normal((e, f, d)) * 0.05, jnp.float32)}
    x = jnp.asarray(rng.standard_normal((8, 16, d)), jnp.float32)

    y_local, _ = moe_layer(p, x, cfg, None)

    mesh = make_mesh()
    ctx = from_mesh(mesh)
    wsh = NamedSharding(mesh, P("model", "data", None))
    p_s = {"router": p["router"], "wg": jax.device_put(p["wg"], wsh),
           "wi": jax.device_put(p["wi"], wsh),
           "wo": jax.device_put(p["wo"], wsh)}
    x_s = jax.device_put(x, NamedSharding(mesh, P("data", "model", None)))
    with mesh:
        y_ep, _ = jax.jit(lambda pp, xx: moe_layer(pp, xx, cfg, ctx))(p_s, x_s)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_local),
                               atol=5e-4, rtol=5e-3)


def test_sharded_train_step_runs(rng):
    cfg = smoke_config("internlm2-1.8b")
    model = Model(cfg)
    mesh = make_mesh()
    ctx = from_mesh(mesh)
    opt = AdamW(OptConfig(warmup_steps=1))
    state = init_state(model, opt, jax.random.PRNGKey(0))
    sh = tree_shardings(ctx, {"params": model.param_axes(),
                              "opt": opt.moment_axes(model.param_axes(),
                                                     model.param_shapes())},
                        jax.eval_shape(lambda: state))
    state = jax.device_put(state, sh)
    B, S = 8, 32
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                   jnp.int32)}
    batch = jax.device_put(batch, NamedSharding(mesh, P("data", None)))
    with mesh:
        step = jax.jit(make_train_step(model, opt, ctx), donate_argnums=0)
        state2, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state2["opt"]["step"]) == 1


def test_production_mesh_shapes():
    # the real 256/512-chip meshes can't be built on 8 host devices; check
    # the constructor signature contract instead
    import repro.launch.mesh as m
    assert m.make_production_mesh.__kwdefaults__ == {"multi_pod": False}


def test_compression_error_feedback(rng):
    from repro.train.compression import ef_compress, ef_decompress
    g = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    res = jnp.zeros_like(g)
    q, scale, res2 = ef_compress(g, res, block=64)
    deq = ef_decompress(q, scale, g.shape)
    # residual telescopes: g == deq + res2
    np.testing.assert_allclose(np.asarray(deq + res2), np.asarray(g),
                               atol=1e-5)
    assert q.dtype == jnp.int8


def test_psum_compressed_under_shard_map(rng):
    from repro.train.compression import psum_compressed
    mesh = make_mesh((8,), ("data",))
    g = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    res = jnp.zeros_like(g)

    def f(gl, rl):
        avg, new_res = psum_compressed(gl[0], rl[0], "data")
        return avg[None], new_res[None]

    with mesh:
        avg, _ = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P("data", None), P("data", None)),
            out_specs=(P("data", None), P("data", None))))(g, res)
    true_avg = np.asarray(g).mean(axis=0)
    got = np.asarray(avg)[0]
    # int8 EF all-reduce: ~1% error on the first step
    np.testing.assert_allclose(got, true_avg, atol=0.05)
