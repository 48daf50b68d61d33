"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantic ground truth: each Pallas kernel's test sweeps
shapes/dtypes and asserts bit-exact agreement against these functions.
Everything here is lossless bit manipulation, so tolerance is exact equality.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "xor_bits",
    "xor_split_planes",
    "merge_planes_xor",
    "hamming_total",
    "byte_split",
    "byte_merge",
]

_UINT_BYTES = {jnp.uint16.dtype: 2, jnp.uint32.dtype: 4, jnp.uint8.dtype: 1, jnp.uint64.dtype: 8}


def _nbytes(dtype) -> int:
    d = jnp.dtype(dtype)
    if d not in _UINT_BYTES:
        raise ValueError(f"expected unsigned int bit-view dtype, got {d}")
    return _UINT_BYTES[d]


def xor_bits(a: jax.Array, b: jax.Array) -> jax.Array:
    """Element-wise XOR of two identically-shaped unsigned-int bit views."""
    assert a.shape == b.shape and a.dtype == b.dtype
    return jnp.bitwise_xor(a, b)


def byte_split(x: jax.Array) -> List[jax.Array]:
    """Split an unsigned-int array into per-byte planes, most significant first.

    For BF16 bit views (uint16) this yields [sign+exp7, exp1+mantissa7] — the
    ZipNN grouping. For FP32 (uint32): 4 planes. Output planes are uint8 arrays
    of the same shape as ``x``.
    """
    nb = _nbytes(x.dtype)
    planes = []
    for k in range(nb - 1, -1, -1):  # MSB plane first
        planes.append(jnp.right_shift(x, jnp.array(8 * k, x.dtype)).astype(jnp.uint8))
    return planes


def byte_merge(planes: List[jax.Array], dtype) -> jax.Array:
    """Inverse of :func:`byte_split`."""
    dtype = jnp.dtype(dtype)
    nb = _nbytes(dtype)
    assert len(planes) == nb
    out = jnp.zeros(planes[0].shape, dtype)
    for i, p in enumerate(planes):
        k = nb - 1 - i
        out = jnp.bitwise_or(out, jnp.left_shift(p.astype(dtype), jnp.array(8 * k, dtype)))
    return out


def xor_split_planes(base: jax.Array, ft: jax.Array) -> List[jax.Array]:
    """Fused BitX encode: XOR two bit views, split the delta into byte planes.

    The hi plane (sign/exponent/upper-mantissa for BF16) is near-all-zero for
    same-family model pairs (paper Fig. 5), which is what makes the downstream
    entropy stage effective.
    """
    return byte_split(xor_bits(base, ft))


def merge_planes_xor(planes: List[jax.Array], base: jax.Array) -> jax.Array:
    """Fused BitX decode: merge byte planes into the XOR delta, XOR with base."""
    delta = byte_merge(planes, base.dtype)
    return jnp.bitwise_xor(delta, base)


def hamming_row_partials(a: jax.Array, b: jax.Array) -> jax.Array:
    """Per-row popcount partial sums (uint32) over 2D bit views.

    A row of up to 2²⁶ bit positions stays far below uint32 overflow; the
    caller finishes the reduction in uint64 on the host (``ops.hamming_total``).
    """
    assert a.shape == b.shape and a.dtype == b.dtype
    pc = jax.lax.population_count(jnp.bitwise_xor(a, b))
    return jnp.sum(pc.astype(jnp.uint32), axis=-1, dtype=jnp.uint32)


def hamming_total(a: jax.Array, b: jax.Array) -> jax.Array:
    """Total number of differing bits between two bit views (uint32 scalar).

    Oracle for test-scale inputs (< 2³² differing bits). The production path
    (``ops.hamming_total``) sums block partials in uint64 on the host, because
    embedding-scale tensors can exceed uint32.
    """
    assert a.shape == b.shape and a.dtype == b.dtype
    pc = jax.lax.population_count(jnp.bitwise_xor(a, b))
    return jnp.sum(pc.astype(jnp.uint32), dtype=jnp.uint32)
