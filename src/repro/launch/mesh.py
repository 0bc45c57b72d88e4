"""Production meshes (TPU v5e numbers).

A function, not a module constant — importing this module never touches jax
device state. Single pod: 16x16 = 256 chips, axes (data, model); multi-pod:
2x16x16 = 512 chips, axes (pod, data, model). The 'pod' axis joins 'data'
for batch/FSDP sharding; 'model' stays within a pod (tensor/expert
parallelism over ICI, never over the cross-pod DCN).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: GSPMD propagates the
    shardings and ``with_sharding_constraint`` accepts the logical-axis
    hints (``make_mesh`` defaults to ``Explicit`` axes, which reject
    both)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """A small mesh over the actually-present devices (tests, examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return _auto_mesh((n // model, model), ("data", "model"))


# TPU v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12      # per chip
HBM_BW = 819e9                # bytes/s per chip
ICI_BW = 50e9                 # bytes/s per link (per chip, one direction)
HBM_PER_CHIP = 16 * 2 ** 30   # 16 GiB
