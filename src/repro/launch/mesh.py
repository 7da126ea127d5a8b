"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Single pod: 16x16 = 256 chips (data x model).
Multi-pod: 2x16x16 = 512 chips (pod x data x model); the "pod" axis carries
only data parallelism + gradient reduction (cross-pod DCI traffic), matching
how multi-slice TPU jobs are actually laid out.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_debug_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(data: int = 2, model: int = 4):
    """Small mesh for CPU multi-device tests (run under
    XLA_FLAGS=--xla_force_host_platform_device_count=N)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
