"""Pallas TPU kernels for ZipNN-style byte-plane shuffling (paper §4.4.3 fallback).

ZipNN groups the bytes of floating-point words so that the highly-redundant
fields (sign+exponent) form contiguous streams for the entropy coder. For BF16
bit views (uint16) that is two planes: [sign|exp7] and [exp_lsb|mantissa7];
for FP32 (uint32), four planes. Unlike BitX these kernels take a *single*
model (no base): they are the no-family fallback compressor and the ZipNN
baseline used in the evaluation.

Same tiling story as ``bitx_xor.py``: lane-local shifts/masks on the VPU,
(block_rows, 1024) VMEM tiles, memory-bound by construction.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.bitx_xor import DEFAULT_BLOCK_ROWS, merge_planes, split_planes

__all__ = ["split_2d", "merge_2d"]


def _split_kernel(x_ref, *plane_refs):
    split_planes(x_ref[...], plane_refs)


def _merge_kernel(*refs):
    plane_refs, o_ref = refs[:-1], refs[-1]
    o_ref[...] = merge_planes(plane_refs).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def split_2d(
    x: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> List[jax.Array]:
    """Split a 2D bit view into uint8 byte planes, MSB first."""
    rows, cols = x.shape
    nb = jnp.dtype(x.dtype).itemsize
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0, (rows, block_rows)
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    out = pl.pallas_call(
        _split_kernel,
        name="byte_split_2d",
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.uint8) for _ in range(nb)],
        in_specs=[spec],
        out_specs=[spec] * nb,
        grid=grid,
        interpret=interpret,
    )(x)
    return list(out)


@functools.partial(jax.jit, static_argnames=("dtype", "block_rows", "interpret"))
def merge_2d(
    planes: Sequence[jax.Array],
    dtype,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jax.Array:
    """Inverse of :func:`split_2d`."""
    dtype = jnp.dtype(dtype)
    nb = dtype.itemsize
    assert len(planes) == nb, (len(planes), nb)
    rows, cols = planes[0].shape
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0, (rows, block_rows)
    grid = (rows // block_rows,)
    spec = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    return pl.pallas_call(
        _merge_kernel,
        name="byte_merge_2d",
        out_shape=jax.ShapeDtypeStruct((rows, cols), dtype),
        in_specs=[spec] * nb,
        out_specs=spec,
        grid=grid,
        interpret=interpret,
    )(*planes)
