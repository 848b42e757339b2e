"""Production meshes.

Single pod : (data=16, model=16)        = 256 chips  (TPU v5e pod slice)
Multi-pod  : (pod=2, data=16, model=16) = 512 chips  (DCN across pods)

Defined as functions so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Whatever this host offers (tests/examples); model axis optional."""
    n = len(jax.devices())
    assert n % model == 0
    return jax.make_mesh(
        (n // model, model), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


# v5e hardware constants used by the roofline analysis
PEAK_FLOPS_BF16 = 197e12  # per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (per-chip figure used in the 3-term model)
