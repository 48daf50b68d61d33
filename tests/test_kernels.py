"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis roundtrip properties.

Everything here is lossless bit manipulation — assertions are EXACT equality.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as stt

from repro.kernels import bitx_xor, byte_planes, hamming, ops, ref

SHAPES = [(1, 1024), (4, 1024), (256, 1024), (3, 2048), (257, 1024)]
DTYPES = [jnp.uint16, jnp.uint32]


def _rand_bits(key, shape, dtype):
    bits = jax.random.randint(key, shape, 0, 2**16, jnp.uint32)
    if dtype == jnp.uint32:
        bits = bits * 65536 + jax.random.randint(key, shape, 0, 2**16, jnp.uint32)
    return bits.astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_xor_split_matches_oracle(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = _rand_bits(k1, shape, dtype)
    b = _rand_bits(k2, shape, dtype)
    rows = shape[0]
    br = rows if rows in (1, 3, 257) else min(256, rows)
    if rows % br:
        br = 1
    got = bitx_xor.xor_split_2d(a, b, block_rows=br, interpret=True)
    want = ref.xor_split_planes(a, b)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("dtype", DTYPES)
def test_merge_xor_roundtrip(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    base = _rand_bits(k1, shape, dtype)
    ft = _rand_bits(k2, shape, dtype)
    br = 1 if shape[0] % 256 else 256
    planes = bitx_xor.xor_split_2d(base, ft, block_rows=br, interpret=True)
    back = bitx_xor.merge_xor_2d(planes, base, block_rows=br, interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(ft))


@pytest.mark.parametrize("shape", SHAPES[:3])
@pytest.mark.parametrize("dtype", DTYPES)
def test_hamming_matches_oracle(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    a = _rand_bits(k1, shape, dtype)
    b = _rand_bits(k2, shape, dtype)
    br = 1 if shape[0] % 256 else 256
    total = hamming.hamming_total_2d(a, b, block_rows=br, interpret=True)
    want = int(ref.hamming_total(a, b))
    assert total == want
    # numpy ground truth
    npw = int(np.bitwise_count(np.asarray(a) ^ np.asarray(b)).astype(np.uint64).sum())
    assert total == npw


@pytest.mark.parametrize("dtype", DTYPES)
def test_byte_planes_roundtrip(dtype):
    x = _rand_bits(jax.random.PRNGKey(3), (8, 1024), dtype)
    planes = byte_planes.split_2d(x, block_rows=8, interpret=True)
    back = byte_planes.merge_2d(planes, dtype, block_rows=8, interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    want = ref.byte_split(x)
    for g, w in zip(planes, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# ---------------------------------------------------------------------------
# ops.py public API: arbitrary shapes/floats, pallas vs jnp-ref vs numpy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("numel,rows", [(1, 1), (1025, 2), (256 * 1024, 256),
                                        (256 * 1024 + 1, 512), (66304 * 1024, 66304)])
def test_packed_rows_pads_to_whole_blocks(numel, rows):
    """Up to one block the whole array is the block; above it rows pad up to
    whole fixed blocks, so any bucket length gets a legal TPU block shape."""
    assert ops.packed_rows(numel) == rows
    br = ops.block_rows_for(rows)
    assert rows % br == 0 and br == min(rows, bitx_xor.DEFAULT_BLOCK_ROWS)


@pytest.mark.parametrize("shape", [(7,), (33, 5), (2, 3, 129), (1025,),
                                   (257 * 1024 + 3,)])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_ops_encode_decode_roundtrip(shape, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(4))
    base = (jax.random.normal(k1, shape, jnp.float32) * 0.02).astype(dtype)
    ft = (base.astype(jnp.float32)
          + jax.random.normal(k2, shape, jnp.float32) * 0.005).astype(dtype)
    for use_pallas in (True, False):
        planes = ops.bitx_encode_planes(base, ft, use_pallas=use_pallas)
        out = ops.bitx_decode_planes(planes, base, use_pallas=use_pallas)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ops.to_bit_view(ft)))


def test_ops_agree_with_numpy_path():
    """Device path and host (paper-C++-equivalent) path are bit-identical."""
    from repro.core.bitx import xor_delta_planes_np
    rng = np.random.RandomState(0)
    base = rng.randn(1000).astype(np.float32)
    ft = (base + rng.randn(1000).astype(np.float32) * 1e-3)
    dev = ops.bitx_encode_planes(jnp.asarray(base), jnp.asarray(ft), use_pallas=True)
    host = xor_delta_planes_np(base, ft)
    for d, h in zip(dev, host):
        np.testing.assert_array_equal(np.asarray(d), h)


@settings(max_examples=30, deadline=None)
@given(stt.lists(stt.floats(width=32, allow_nan=True, allow_infinity=True),
                 min_size=1, max_size=300))
def test_property_bitx_roundtrip_any_floats(xs):
    """BitX is lossless for ANY bit pattern, including NaN/Inf payloads."""
    base = np.asarray(xs, np.float32)
    ft = base[::-1].copy()
    planes = ops.bitx_encode_planes(jnp.asarray(base), jnp.asarray(ft), use_pallas=True)
    out = ops.bitx_decode_planes(planes, jnp.asarray(base), use_pallas=True)
    np.testing.assert_array_equal(np.asarray(out), ft.view(np.uint32))


@settings(max_examples=20, deadline=None)
@given(stt.integers(1, 5000), stt.integers(0, 2**32 - 1))
def test_property_hamming_symmetry_and_identity(n, seed):
    rng = np.random.RandomState(seed % 2**31)
    a = rng.randint(0, 2**16, n).astype(np.uint16)
    b = rng.randint(0, 2**16, n).astype(np.uint16)
    ja, jb = jnp.asarray(a), jnp.asarray(b)
    assert ops.hamming_total(ja, ja) == 0
    assert ops.hamming_total(ja, jb) == ops.hamming_total(jb, ja)
    assert ops.bit_distance(ja, jb) <= 16.0
