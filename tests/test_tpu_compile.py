"""Compile the storage-path Pallas kernels for a described TPU v5e.

Interpret mode on the CPU cannot show what the TPU compiler (Mosaic) refuses:
16-bit shifts, block shapes off the (8, 128) tiling rule. These tests lower
and compile each main-path kernel at real widths for a v5e that is described,
not attached, using the row padding and row-block of ``repro.kernels.ops``.
Nothing runs, so they say nothing about results or speed.

The topology is described inside a module-scoped fixture (only the worker
that runs this file loads the TPU compiler), and the persistent compilation
cache is off around these compiles: an entry compiled for a described chip
cannot be read back without one.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitx_xor, byte_planes, ops

# (bit-view dtype, elements): a qwen2-7b MLP projection in bf16 (18944 x 3584,
# 66304 rows), an odd length whose rows pad up to whole blocks, one fp32
# and one 8-bit bucket
CASES = [
    ("uint16", 18944 * 3584),
    ("uint16", 1000 * 1024 + 7),
    ("uint32", 4096 * 1024 + 1),
    ("uint8", 3 * 512 * 1024 + 9),
]
KERNELS = ["xor_split_2d", "merge_xor_2d", "split_2d", "merge_2d"]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # no compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            cc.reset_cache()


def _lower(kernel: str, words, planes, nb: int, block_rows: int):
    if kernel == "xor_split_2d":
        return bitx_xor.xor_split_2d.lower(words, words, block_rows=block_rows,
                                           interpret=False)
    if kernel == "merge_xor_2d":
        return bitx_xor.merge_xor_2d.lower([planes] * nb, words,
                                           block_rows=block_rows, interpret=False)
    if kernel == "split_2d":
        return byte_planes.split_2d.lower(words, block_rows=block_rows,
                                          interpret=False)
    return byte_planes.merge_2d.lower([planes] * nb, words.dtype,
                                      block_rows=block_rows, interpret=False)


@pytest.mark.parametrize("dtype,numel", CASES)
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel, dtype, numel):
    rows = ops.packed_rows(numel)
    block_rows = ops.block_rows_for(rows)
    assert rows % block_rows == 0
    words = jax.ShapeDtypeStruct((rows, ops.LANES), jnp.dtype(dtype),
                                 sharding=one_chip)
    planes = jax.ShapeDtypeStruct((rows, ops.LANES), jnp.uint8,
                                  sharding=one_chip)
    compiled = _lower(kernel, words, planes, jnp.dtype(dtype).itemsize,
                      block_rows).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the name each kernel's custom call carries in the device trace, whatever
# the Python function around it is called
KERNEL_NAMES = {"xor_split_2d": "xor_split_2d", "merge_xor_2d": "merge_xor_2d",
                "split_2d": "byte_split_2d", "merge_2d": "byte_merge_2d"}


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_custom_call_is_named(one_chip, kernel):
    rows = ops.packed_rows(1 << 18)
    words = jax.ShapeDtypeStruct((rows, ops.LANES), jnp.uint16, sharding=one_chip)
    planes = jax.ShapeDtypeStruct((rows, ops.LANES), jnp.uint8, sharding=one_chip)
    text = _lower(kernel, words, planes, 2, ops.block_rows_for(rows)).compile().as_text()
    calls = [line.split(" = ")[0].split()[-1] for line in text.splitlines()
             if "tpu_custom_call" in line]
    assert calls and all(c.startswith(f"%{KERNEL_NAMES[kernel]}.") for c in calls), calls
