"""Production mesh construction.

Defined as a FUNCTION (not a module constant) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def dp_axes(multi_pod: bool) -> tuple[str, ...]:
    """Axes that carry data parallelism (batch + ZeRO-1 + grad reduction)."""
    return ("pod", "data") if multi_pod else ("data",)
