"""Production meshes (assignment-mandated geometry).

Single pod: 16×16 = 256 chips, axes ("data", "model").
Multi-pod:  2×16×16 = 512 chips, axes ("pod", "data", "model").

Defined as functions so importing this module never touches jax device
state: a caller may still set XLA_FLAGS before jax initializes.
"""

from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(shape=None, axes=None):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    n = len(jax.devices())
    if shape is None:
        shape, axes = (n,), ("data",)
    return _auto_mesh(shape, axes)
