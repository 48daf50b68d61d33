"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — ``jax.make_mesh`` is only called by the
dry-run driver (which forces 512 host devices) or by tests (which build tiny
local meshes).

Production topology (TPU v5e-like):

* single-pod: 16 × 16 = 256 chips, axes ("data", "model")
* multi-pod:  2 × 16 × 16 = 512 chips, axes ("pod", "data", "model")

The "model" axis carries TP + sequence-parallel decode; "data" carries DP +
FSDP; "pod" carries DP (and optionally FSDP for grok-scale models — see
``ShardingRules.for_mesh(fsdp_over_pod=True)``).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_local_mesh", "HW"]


def _mk(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1, pod: int = 0):
    """Small mesh over however many devices the test process has."""
    if pod:
        return _mk((pod, data, model), ("pod", "data", "model"))
    return _mk((data, model), ("data", "model"))


class HW:
    """TPU v5e-like hardware constants for the roofline model."""

    PEAK_FLOPS_BF16 = 197e12     # per chip
    HBM_BW = 819e9               # bytes/s per chip
    ICI_BW_PER_LINK = 50e9       # bytes/s per link (~)
    HBM_BYTES = 16 * 2**30       # 16 GiB per chip
