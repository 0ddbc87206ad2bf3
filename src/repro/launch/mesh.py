"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first jax
init; smoke tests and benches see 1 device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """A mesh whose axes GSPMD partitions: the dry-run steers layouts with
    ``with_sharding_constraint``, which refuses the Explicit axes
    ``jax.make_mesh`` gives by default."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ("data", "model") single-pod; ("pod", "data", "model") multi-pod.
    "model" is the fast intra-pod ICI plane (per-layer TP/EP collectives);
    "pod" is the slow DCN plane (gradient reduction only) — DESIGN.md §3.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_dev_mesh(n_data: int = 2, n_model: int = 4, *, multi_pod: bool = False):
    """Small mesh over host devices for integration tests."""
    if multi_pod:
        return _auto_mesh((2, n_data, n_model), ("pod", "data", "model"))
    return _auto_mesh((n_data, n_model), ("data", "model"))
