"""Production meshes.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* any jax
import; smoke tests and benches see the real single CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256-chip pod (data, model); 2x16x16 = 512-chip two-pod mesh."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many host devices exist (tests)."""
    return _make_mesh((data, model), ("data", "model"))
