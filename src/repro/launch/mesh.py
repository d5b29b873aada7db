"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing never touches jax
device state — the dry-run must set XLA_FLAGS before any jax init.
"""

from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_test_mesh"]


def _auto(n_axes: int) -> tuple:
    # every axis Auto: the sharding plan's in/out shardings drive GSPMD
    return (jax.sharding.AxisType.Auto,) * n_axes


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model); the `pod` axis is
    pure DP across the datacenter interconnect."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_test_mesh(data: int = 2, model: int = 4):
    """Small mesh for CPU tests (requires >= data*model fake devices)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))
