"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not module-level state) so importing
this module never touches jax device state. The dry-run launcher sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; nothing else in the codebase does.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh for CPU tests (1 device unless the test forced more)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
