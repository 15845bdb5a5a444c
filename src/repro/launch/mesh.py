"""Production mesh construction.

Functions, not module-level constants — importing this module never touches
jax device state.  The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` BEFORE importing
jax; tests and benchmarks see the real single CPU device.
"""
from __future__ import annotations

import jax

from repro.sharding.partition import MeshAxes


def _auto_axes(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, **_auto_axes(len(axes)))


def mesh_axes(*, multi_pod: bool = False) -> MeshAxes:
    return MeshAxes(pod="pod" if multi_pod else None)


def make_debug_mesh(n_data: int = 2, n_model: int = 4):
    """Small host-device mesh for sharding tests."""
    return jax.make_mesh((n_data, n_model), ("data", "model"),
                         **_auto_axes(2))


# TPU v5e hardware constants (roofline targets; DESIGN.md §3)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
