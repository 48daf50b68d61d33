"""Pallas TPU kernels for BitX encode/decode (paper §4.3).

Encode: ``delta = base ^ ft`` fused with a byte-plane split of the delta.
Decode: merge byte planes back into the delta and XOR with the base.

TPU adaptation (DESIGN.md §3): the paper's C++ implementation streams bytes on
a CPU. On TPU the tensors are already resident in HBM (e.g. when a checkpoint
is being taken), so we tile them through VMEM and do XOR + shift/mask plane
extraction on the VPU. Plane extraction is a pure lane-local shift — no
gather/scatter — so the kernel is memory-bound by design: one HBM read per
input, one write per plane. Blocks are (block_rows, 1024): the lane dim is a
multiple of both the VPU lane width (128) and the dtype packing, and a
256×1024 uint16 tile is 512 KiB — three such tiles (two in, planes out) sit
comfortably in the ~16 MiB of VMEM of a v5e core.

All kernels operate on 2D unsigned-int bit views; ``ops.py`` owns the
flatten/pad/bitcast plumbing and the interpret-mode fallback used for CPU
validation.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "xor_split_2d",
    "merge_xor_2d",
    "split_planes",
    "merge_planes",
    "DEFAULT_BLOCK_ROWS",
    "LANES",
]

LANES = 1024  # second-minor tile dim; multiple of the 128-lane VPU width
DEFAULT_BLOCK_ROWS = 256


# Mosaic has no 16-bit shifts (``arith.shrui``/``arith.shli`` on i16 fail to
# legalize), so plane extraction and assembly run on words widened to 32
# bits; the narrowing casts at either end are exact.

def split_planes(word: jax.Array, plane_refs) -> None:
    """Write the byte planes of ``word`` (MSB first) into ``plane_refs``."""
    w = word.astype(jnp.uint32)
    nb = len(plane_refs)
    for i, p_ref in enumerate(plane_refs):
        p_ref[...] = jnp.right_shift(w, jnp.uint32(8 * (nb - 1 - i))).astype(jnp.uint8)


def merge_planes(plane_refs) -> jax.Array:
    """Assemble byte planes (MSB first) into a uint32 word."""
    nb = len(plane_refs)
    w = jnp.zeros(plane_refs[0].shape, jnp.uint32)
    for i, p_ref in enumerate(plane_refs):
        w = jnp.bitwise_or(w, jnp.left_shift(p_ref[...].astype(jnp.uint32),
                                             jnp.uint32(8 * (nb - 1 - i))))
    return w


def _xor_split_kernel(a_ref, b_ref, *plane_refs):
    """XOR + byte-plane split, MSB plane first."""
    split_planes(jnp.bitwise_xor(a_ref[...], b_ref[...]), plane_refs)


def _merge_xor_kernel(base_ref, *refs):
    """planes (MSB first) + base -> ft bits. Last ref is the output."""
    plane_refs, o_ref = refs[:-1], refs[-1]
    delta = merge_planes(plane_refs).astype(o_ref.dtype)
    o_ref[...] = jnp.bitwise_xor(delta, base_ref[...])


def _row_blockspec(block_rows: int, cols: int) -> pl.BlockSpec:
    return pl.BlockSpec((block_rows, cols), lambda i: (i, 0))


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def xor_split_2d(
    base: jax.Array,
    ft: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> List[jax.Array]:
    """Fused BitX encode over a 2D bit view. Returns byte planes, MSB first."""
    rows, cols = base.shape
    nb = jnp.dtype(base.dtype).itemsize
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0, (rows, block_rows)
    grid = (rows // block_rows,)
    spec = _row_blockspec(block_rows, cols)
    out = pl.pallas_call(
        _xor_split_kernel,
        name="xor_split_2d",
        out_shape=[jax.ShapeDtypeStruct(base.shape, jnp.uint8) for _ in range(nb)],
        in_specs=[spec, spec],
        out_specs=[spec] * nb,
        grid=grid,
        interpret=interpret,
    )(base, ft)
    return list(out)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def merge_xor_2d(
    planes: Sequence[jax.Array],
    base: jax.Array,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jax.Array:
    """Fused BitX decode over a 2D bit view: planes (MSB first) + base -> ft."""
    rows, cols = base.shape
    nb = jnp.dtype(base.dtype).itemsize
    assert len(planes) == nb, (len(planes), nb)
    block_rows = min(block_rows, rows)
    assert rows % block_rows == 0, (rows, block_rows)
    grid = (rows // block_rows,)
    spec = _row_blockspec(block_rows, cols)
    return pl.pallas_call(
        _merge_xor_kernel,
        name="merge_xor_2d",
        out_shape=jax.ShapeDtypeStruct(base.shape, base.dtype),
        in_specs=[spec] * (1 + nb),
        out_specs=spec,
        grid=grid,
        interpret=interpret,
    )(base, *planes)
