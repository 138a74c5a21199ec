"""Production mesh factory (assignment-mandated shape).

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (required so smoke tests see 1 device while the
dry-run sees 512)."""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "POD_CHIPS"]

POD_CHIPS = 256  # 16×16 v5e pod


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(shape))
