"""Loop-aware HLO cost model: FLOPs vs analytic ground truth."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import hlo_cost
from repro.launch.roofline import Roofline, parse_collectives


def test_scan_flops_multiplied():
    """A scan of L matmuls must be charged L*flops, not 1x (the XLA
    cost_analysis undercount this module exists to fix)."""
    L, n = 16, 64

    def f(x, ws):
        def body(c, w):
            return jnp.dot(c, w, preferred_element_type=jnp.float32), None
        y, _ = jax.lax.scan(body, x, ws)
        return y

    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((n, n), jnp.float32),
        jax.ShapeDtypeStruct((L, n, n), jnp.float32)).compile()
    cost = hlo_cost.analyze(compiled.as_text())
    analytic = 2.0 * L * n ** 3
    assert cost.flops == pytest.approx(analytic, rel=0.2)
    xla = (compiled.cost_analysis() or {}).get("flops", 0.0)
    assert xla < 0.5 * analytic  # the undercount we correct


def test_collectives_counted_with_wire_factors():
    devs = jax.devices()
    mesh = jax.make_mesh((8,), ("d",),
                         axis_types=(jax.sharding.AxisType.Auto,))

    def f(x):
        return jnp.sum(x, axis=0)  # contraction over sharded dim -> psum

    sh = NamedSharding(mesh, P("d", None))
    with mesh:
        compiled = jax.jit(f, in_shardings=(sh,),
                           out_shardings=NamedSharding(mesh, P())).lower(
            jax.ShapeDtypeStruct((8, 1024), jnp.float32)).compile()
    cost = hlo_cost.analyze(compiled.as_text())
    assert "all-reduce" in cost.coll
    rec = cost.coll["all-reduce"]
    # 1024 f32 all-reduced over 8: wire = 2 * 4096 * 7/8
    assert rec["wire_bytes"] == pytest.approx(2 * 4096 * 7 / 8, rel=0.01)
    # legacy text parser agrees on op identification
    legacy = parse_collectives(compiled.as_text())
    assert "all-reduce" in legacy


def test_roofline_terms_and_bottleneck():
    rl = Roofline(flops_per_chip=197e12, bytes_per_chip=819e9 * 2,
                  wire_bytes_per_chip=50e9 * 0.5, collectives={},
                  model_flops_total=197e12 * 256, chips=256)
    assert rl.compute_s == pytest.approx(1.0)
    assert rl.memory_s == pytest.approx(2.0)
    assert rl.collective_s == pytest.approx(0.5)
    assert rl.bottleneck == "memory"
    assert rl.roofline_fraction == pytest.approx(0.5)
    assert rl.useful_flops_ratio == pytest.approx(1.0)
