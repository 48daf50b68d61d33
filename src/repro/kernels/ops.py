"""Public jit'd API over the Pallas kernels, with shape/dtype plumbing.

Callers hand in arbitrary-shaped arrays (float or bit-view); this module owns:

* bitcasting floats to unsigned bit views (bf16→u16, f32→u32, …),
* flattening + padding to (rows, 1024) tiles the kernels expect, with rows
  padded to a whole number of fixed row-blocks (:func:`packed_rows`),
* ``interpret=True`` on the CPU backend only (tests validate the kernel
  bodies there); elsewhere the kernels compile or fail,
* un-padding / reshaping results back.

Each public transform is one jitted program per input shape: padding, the
kernel and the un-padding compile together.

A pure-numpy path (``backend="numpy"``) is also provided: the storage pipeline
uses it for host-side ingestion of mmap'd tensors where device transfer would
dominate; tests assert the numpy, jnp-ref and Pallas paths agree bit-exactly.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bitx_xor as _bitx
from repro.kernels import byte_planes as _bp
from repro.kernels import hamming as _ham
from repro.kernels import ref as _ref

__all__ = [
    "bit_view_dtype",
    "to_bit_view",
    "bitx_encode_planes",
    "bitx_decode_planes",
    "zipnn_split_planes",
    "zipnn_merge_planes",
    "hamming_total",
    "bit_distance",
]

LANES = _bitx.LANES

_FLOAT_TO_UINT = {
    "bfloat16": jnp.uint16,
    "float16": jnp.uint16,
    "float32": jnp.uint32,
    "float64": jnp.uint64,
}


def _interpret() -> bool:
    """Interpret mode on the CPU backend only (tests run the kernel bodies
    there); on any other backend the kernels compile or fail."""
    return jax.default_backend() == "cpu"


def bit_view_dtype(dtype) -> jnp.dtype:
    """Unsigned bit-view dtype for a float (or passthrough for uints)."""
    d = jnp.dtype(dtype)
    if d.name in _FLOAT_TO_UINT:
        return jnp.dtype(_FLOAT_TO_UINT[d.name])
    if d.kind == "u":
        return d
    raise ValueError(f"no bit view for dtype {d}")


def to_bit_view(x: jax.Array) -> jax.Array:
    """Bitcast to the unsigned view (no-op if already unsigned)."""
    tgt = bit_view_dtype(x.dtype)
    if x.dtype == tgt:
        return x
    return jax.lax.bitcast_convert_type(x, tgt)


def packed_rows(numel: int) -> int:
    """Rows of the (rows, LANES) tile view of ``numel`` elements. Up to one
    block the whole array is one block (a block equal to the full array
    passes Mosaic's tiling rule at any row count); above it, rows pad up to
    a multiple of ``DEFAULT_BLOCK_ROWS`` so every block obeys the rule."""
    rows = max(1, -(-numel // LANES))
    if rows <= _bitx.DEFAULT_BLOCK_ROWS:
        return rows
    return -(-rows // _bitx.DEFAULT_BLOCK_ROWS) * _bitx.DEFAULT_BLOCK_ROWS


def block_rows_for(rows: int) -> int:
    """Row-block of a :func:`packed_rows` view (it always divides ``rows``)."""
    return min(rows, _bitx.DEFAULT_BLOCK_ROWS)


def _pack_2d(x: jax.Array, rows: int) -> jax.Array:
    """Flatten + zero-pad to (rows, LANES)."""
    flat = x.reshape(-1)
    pad = rows * LANES - flat.shape[0]
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, LANES)


# ---------------------------------------------------------------------------
# BitX encode / decode
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("use_pallas",))
def bitx_encode_planes(base: jax.Array, ft: jax.Array, *, use_pallas: bool = True) -> List[jax.Array]:
    """XOR-delta byte planes (MSB first) of ``ft`` against ``base``.

    Accepts float or bit-view arrays of identical shape/dtype; returns flat
    uint8 planes of length ``numel(base)``.
    """
    a = to_bit_view(jnp.asarray(base))
    b = to_bit_view(jnp.asarray(ft))
    assert a.shape == b.shape and a.dtype == b.dtype, (a.shape, b.shape, a.dtype, b.dtype)
    n = a.size
    rows = packed_rows(n)
    a2, b2 = _pack_2d(a, rows), _pack_2d(b, rows)
    if use_pallas:
        planes = _bitx.xor_split_2d(a2, b2, block_rows=block_rows_for(rows),
                                    interpret=_interpret())
    else:
        planes = _ref.xor_split_planes(a2, b2)
    return [p.reshape(-1)[:n] for p in planes]


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def bitx_decode_planes(planes: Sequence[jax.Array], base: jax.Array, *, use_pallas: bool = True) -> jax.Array:
    """Inverse of :func:`bitx_encode_planes`; returns the bit view of ``ft``
    with the same shape as ``base``."""
    a = to_bit_view(jnp.asarray(base))
    n = a.size
    rows = packed_rows(n)
    a2 = _pack_2d(a, rows)
    padded = [_pack_2d(jnp.asarray(p), rows) for p in planes]
    if use_pallas:
        out = _bitx.merge_xor_2d(padded, a2, block_rows=block_rows_for(rows),
                                 interpret=_interpret())
    else:
        out = _ref.merge_planes_xor(padded, a2)
    return out.reshape(-1)[:n].reshape(a.shape)


# ---------------------------------------------------------------------------
# ZipNN byte planes (single model, no base)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("use_pallas",))
def zipnn_split_planes(x: jax.Array, *, use_pallas: bool = True) -> List[jax.Array]:
    a = to_bit_view(jnp.asarray(x))
    n = a.size
    rows = packed_rows(n)
    a2 = _pack_2d(a, rows)
    if use_pallas:
        planes = _bp.split_2d(a2, block_rows=block_rows_for(rows), interpret=_interpret())
    else:
        planes = _ref.byte_split(a2)
    return [p.reshape(-1)[:n] for p in planes]


@functools.partial(jax.jit, static_argnames=("dtype", "shape", "use_pallas"))
def zipnn_merge_planes(planes: Sequence[jax.Array], dtype, shape, *, use_pallas: bool = True) -> jax.Array:
    dtype = bit_view_dtype(dtype)
    numel = 1
    for s in shape:
        numel *= s
    rows = packed_rows(numel)
    padded = [_pack_2d(jnp.asarray(p), rows) for p in planes]
    if use_pallas:
        out = _bp.merge_2d(padded, dtype, block_rows=block_rows_for(rows), interpret=_interpret())
    else:
        out = _ref.byte_merge(padded, dtype)
    return out.reshape(-1)[:numel].reshape(shape)


# ---------------------------------------------------------------------------
# Bit distance
# ---------------------------------------------------------------------------

def hamming_total(a: jax.Array, b: jax.Array, *, use_pallas: bool = True) -> int:
    """Total differing bits between two same-shape arrays (exact, uint64-safe)."""
    av = to_bit_view(jnp.asarray(a))
    bv = to_bit_view(jnp.asarray(b))
    assert av.shape == bv.shape and av.dtype == bv.dtype
    rows = packed_rows(av.size)
    a2 = _pack_2d(av, rows)
    b2 = _pack_2d(bv, rows)  # identical zero padding cancels in XOR
    if use_pallas:
        partials = _ham.hamming_partials_2d(
            a2, b2, block_rows=block_rows_for(rows), interpret=_interpret()
        )
    else:
        partials = _ref.hamming_row_partials(a2, b2)
    return int(np.asarray(partials).astype(np.uint64).sum())


def bit_distance(a: jax.Array, b: jax.Array, *, use_pallas: bool = True) -> float:
    """Paper Eq. 1: mean differing bits per element."""
    n = int(np.prod(a.shape)) if hasattr(a, "shape") else int(np.asarray(a).size)
    total = hamming_total(a, b, use_pallas=use_pallas)
    return float(total) / float(max(n, 1))
