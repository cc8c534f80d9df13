"""Production mesh builders.

Functions (never module-level constants) so importing this module never
touches jax device state.  Single pod = (16, 16) data x model (256 chips,
one v5e pod); multi-pod adds a leading ``pod`` axis (2 x 256 = 512).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes (its default became Explicit,
    which ``with_sharding_constraint`` rejects)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    data = min(data, n)
    model = max(1, min(model, n // data))
    return _auto_mesh((data, model), ("data", "model"))
