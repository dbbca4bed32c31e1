"""QTensor pytree behavior + int4 pack/unpack round-trip (no hypothesis)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.quantize import (QTensor, pack_codes_int4, quantize_blockwise,
                                 unpack_codes_int4)


def _random_codes(rng, n):
    """Valid MSB codes: sign * (level + 1), level in [0, 8), plus exact 0."""
    lv = rng.integers(0, 8, n)
    sign = rng.choice([-1, 1], n)
    codes = (sign * (lv + 1)).astype(np.int8)
    codes[rng.random(n) < 0.1] = 0
    return codes


def test_pack_unpack_round_trip_nonzero(rng):
    codes = _random_codes(rng, 512)
    codes[codes == 0] = 1                       # zero-free: exact round trip
    packed = pack_codes_int4(jnp.asarray(codes))
    assert packed.dtype == jnp.uint8 and packed.shape == (256,)
    out = np.asarray(unpack_codes_int4(packed, codes.shape))
    np.testing.assert_array_equal(out, codes)


def test_pack_unpack_negative_codes(rng):
    codes = -np.arange(1, 9, dtype=np.int8).repeat(8)   # all 8 negative codes
    out = np.asarray(unpack_codes_int4(
        pack_codes_int4(jnp.asarray(codes)), codes.shape))
    np.testing.assert_array_equal(out, codes)


def test_pack_zero_densifies_to_code_one(rng):
    """Exact zeros (code 0) pack as level-0/sign+ and unpack as +1: the
    packed path trades the zero special-case for density (DESIGN.md §7)."""
    codes = _random_codes(rng, 256)
    out = np.asarray(unpack_codes_int4(
        pack_codes_int4(jnp.asarray(codes)), codes.shape))
    nz = codes != 0
    np.testing.assert_array_equal(out[nz], codes[nz])
    np.testing.assert_array_equal(out[~nz], np.ones((~nz).sum(), np.int8))


def test_pack_rejects_odd_length():
    with pytest.raises(ValueError):
        pack_codes_int4(jnp.ones((3,), jnp.int8))
    with pytest.raises(ValueError):              # not whole 64-blocks
        pack_codes_int4(jnp.ones((96,), jnp.int8))


def test_pack_layout_pairs_block_halves():
    """Byte j of each 64-block holds elements j (low nibble) and j + 32
    (high nibble): the layout the kernel unpacks without a lane interleave."""
    codes = np.ones(128, np.int8)
    codes[[0, 32 + 5, 64 + 31, 127]] = [2, -3, 4, -8]
    packed = np.asarray(pack_codes_int4(jnp.asarray(codes)))
    assert packed.shape == (64,)
    assert packed[0] == 0x01                    # lo: level 1; hi: code 1
    assert packed[5] == (0b1010 << 4)           # hi: sign | level 2
    assert packed[32 + 31] == (0b1111 << 4) | 3


def test_pack_unpack_2d_shape(rng):
    codes = _random_codes(rng, 512).reshape(8, 64)
    codes[codes == 0] = 2
    out = np.asarray(unpack_codes_int4(
        pack_codes_int4(jnp.asarray(codes)), codes.shape))
    np.testing.assert_array_equal(out, codes)


# ---------------------------------------------------------------------------
# QTensor as a pytree
# ---------------------------------------------------------------------------

def _make_qtensor(rng):
    w = jnp.asarray(rng.standard_normal((8, 128)), jnp.float32)
    return w, quantize_blockwise(w, bits=4, block=64, solver="kmeans")


def test_qtensor_flatten_unflatten_identity(rng):
    _, q = _make_qtensor(rng)
    leaves, treedef = jax.tree_util.tree_flatten(q)
    assert len(leaves) == 2                      # codes, scales
    q2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(q2, QTensor)
    assert (q2.bits, q2.block, q2.dtype) == (q.bits, q.block, q.dtype)
    np.testing.assert_array_equal(np.asarray(q2.codes), np.asarray(q.codes))
    np.testing.assert_array_equal(np.asarray(q2.scales), np.asarray(q.scales))


def test_qtensor_through_jit(rng):
    """QTensor crosses the jit boundary as a pytree argument AND return
    value; dequantize inside jit matches eager."""
    _, q = _make_qtensor(rng)

    @jax.jit
    def f(qt):
        return qt, qt.dequantize()

    q2, deq = f(q)
    assert isinstance(q2, QTensor) and q2.bits == q.bits
    np.testing.assert_allclose(np.asarray(deq), np.asarray(q.dequantize()),
                               rtol=1e-6)
    # static aux data means retracing only on bits/block/dtype change
    assert f._cache_size() == 1
    f(q2)
    assert f._cache_size() == 1


def test_qtensor_tree_map_touches_leaves(rng):
    _, q = _make_qtensor(rng)
    doubled = jax.tree_util.tree_map(lambda x: x * 2, q)
    np.testing.assert_array_equal(np.asarray(doubled.codes),
                                  2 * np.asarray(q.codes))
