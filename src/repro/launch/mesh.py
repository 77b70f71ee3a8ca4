"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first jax
initialization, and smoke tests must see 1 device.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax


def _auto(axes):
    return (jax.sharding.AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod: (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_host_mesh(shape: Tuple[int, ...] = (1, 1),
                   axes: Tuple[str, ...] = ("data", "model")):
    """Small mesh over however many (host) devices exist — tests/examples."""
    n = 1
    for s in shape:
        n *= s
    devs = jax.devices()[:n]
    return jax.make_mesh(shape, axes, axis_types=_auto(axes), devices=devs)


# TPU v5e hardware constants (roofline targets; per assignment)
PEAK_FLOPS_BF16 = 197e12        # per chip
HBM_BW = 819e9                  # bytes/s per chip
ICI_BW = 50e9                   # bytes/s per link
