"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — ``dryrun.py`` must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first jax
init, and smoke tests must keep seeing 1 device.

"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis typed Auto (GSPMD propagation)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1):
    """Tiny mesh over however many (possibly forced-host) devices exist."""
    return make_mesh((n_data, n_model), ("data", "model"))
