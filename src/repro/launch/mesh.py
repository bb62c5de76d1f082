"""Production mesh builders (functions, never module-level constants — no
jax device-state touch at import time).

Every mesh uses ``Auto`` axes: the sharding code here places arrays and
lets GSPMD propagate, so shardings must stay out of the array types
(``jax.make_mesh`` otherwise defaults to ``Explicit`` axes).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def _auto(n: int):
    return (jax.sharding.AxisType.Auto,) * n


def make_local_mesh():
    """1-device mesh with the same axis names (CPU smoke / examples)."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(2))
