"""Compile rehearsal: ``msb_matmul`` built for a described TPU v5e.

No chip is attached. The TPU compiler compiles for a described ``v5e:2x2``
topology, so tiling and VMEM rejections that interpret mode cannot see
fail here, at no chip time. Shapes are the ones the serving engine
dispatches for internlm2-1.8b at its published widths (d_model 2048,
d_ff 8192, vocab padded to 92672): decode rows, a prefill bucket, and the
k-blocked unembedding with an f32 ``x``, whole and as a tp=4 shard.

The topology is described only inside a fixture: a described TPU loads
libtpu, which one process at a time may hold.
"""
import os
import time

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.msb_matmul.msb_matmul import BLOCK, LEVELS, msb_matmul


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (m, k, n, x dtype, kblocked): internlm2-1.8b dispatch shapes
SHAPES = {
    "qkv_decode_m1": (1, 2048, 2048, jnp.bfloat16, False),
    "mlp_up_decode_m8": (8, 2048, 8192, jnp.bfloat16, False),
    "mlp_down_prefill_m512": (512, 8192, 2048, jnp.bfloat16, False),
    "unembed_decode_m8_f32": (8, 2048, 92672, jnp.float32, True),
    "unembed_tp4_shard_m8_f32": (8, 2048, 23296, jnp.float32, True),
}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_msb_matmul_compiles_for_v5e(one_chip, no_persistent_cache, name):
    m, k, n, dtype, kblocked = SHAPES[name]
    scales = ((k // BLOCK, LEVELS, n) if kblocked
              else (n // BLOCK, LEVELS, k))
    args = [jax.ShapeDtypeStruct((m, k), dtype, sharding=one_chip),
            jax.ShapeDtypeStruct((k, n // 2), jnp.uint8, sharding=one_chip),
            jax.ShapeDtypeStruct(scales, jnp.float32, sharding=one_chip)]
    t0 = time.monotonic()
    compiled = msb_matmul.lower(*args, kblocked=kblocked).compile()
    seconds = time.monotonic() - t0
    assert "tpu_custom_call" in compiled.as_text()
    assert seconds < 60, f"{name}: compile took {seconds:.1f}s"
