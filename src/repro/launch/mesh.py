"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state): single-pod (16, 16) = 256 chips, multi-pod (2, 16, 16) =
512 chips across a DCN 'pod' axis. The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; everything else in the repo sees the real device count.
"""
from __future__ import annotations

import jax

from repro.utils import make_mesh


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(data: int = 2, model: int = 2) -> jax.sharding.Mesh:
    """Small mesh for multi-device subprocess tests."""
    return make_mesh((data, model), ("data", "model"))


# Hardware model for the roofline (TPU v5e-class, per assignment):
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link (we report per-chip wire bytes / this)
DCN_BW = 6.25e9                 # bytes/s per chip across pods (assumed, noted)
