"""Checkpointer: atomicity, integrity, elastic resharding."""
import json
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.train import Checkpointer


@pytest.fixture
def state(rng):
    return {"params": {"w": jnp.asarray(rng.standard_normal((8, 16)),
                                        jnp.float32),
                       "b": jnp.zeros((16,), jnp.float32)},
            "step": jnp.asarray(7, jnp.int32)}


def test_save_restore(state, tmp_path):
    ck = Checkpointer(tmp_path, async_save=False)
    ck.save(state, 10)
    restored, step = ck.restore_latest(like=state)
    assert step == 10
    np.testing.assert_allclose(restored["params"]["w"],
                               np.asarray(state["params"]["w"]))


def test_async_save(state, tmp_path):
    ck = Checkpointer(tmp_path, async_save=True)
    ck.save(state, 1)
    ck.wait()
    assert ck.steps() == [1]


def test_keep_policy(state, tmp_path):
    ck = Checkpointer(tmp_path, keep=2, async_save=False)
    for s in (1, 2, 3, 4):
        ck.save(state, s)
    assert ck.steps() == [3, 4]


def test_corruption_detected(state, tmp_path):
    ck = Checkpointer(tmp_path, async_save=False)
    ck.save(state, 5)
    # flip bytes in one leaf
    d = tmp_path / "step_5"
    f = sorted(p for p in os.listdir(d) if p.endswith(".npy"))[0]
    path = d / f
    raw = bytearray(path.read_bytes())
    raw[-4] ^= 0xFF
    path.write_bytes(bytes(raw))
    with pytest.raises(IOError, match="corruption"):
        ck.restore(5, like=state)


def test_incomplete_checkpoint_ignored(state, tmp_path):
    ck = Checkpointer(tmp_path, async_save=False)
    ck.save(state, 3)
    os.makedirs(tmp_path / "step_9.tmp", exist_ok=True)  # crashed write
    assert ck.steps() == [3]


def test_elastic_reshard(state, tmp_path):
    """Save under one sharding, restore onto a different mesh layout."""
    devs = jax.devices()
    assert len(devs) >= 8
    mesh_a = jax.make_mesh((4, 2), ("data", "model"),
                           axis_types=(jax.sharding.AxisType.Auto,) * 2)
    mesh_b = jax.make_mesh((2,), ("data",),
                           axis_types=(jax.sharding.AxisType.Auto,),
                           devices=devs[:2])
    sharded = jax.device_put(
        state["params"]["w"], NamedSharding(mesh_a, P("data", "model")))
    ck = Checkpointer(tmp_path, async_save=False)
    ck.save({"w": sharded}, 1)
    target = NamedSharding(mesh_b, P("data", None))
    restored, _ = ck.restore(1, like={"w": np.zeros((8, 16), np.float32)},
                             shardings={"w": target})
    assert restored["w"].sharding == target
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(state["params"]["w"]))
