"""Production mesh construction.

Single pod: (16, 16) = ("data", "model") — 256 chips (v5e pod).
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips.

A FUNCTION, not a module-level constant: importing this module must never
touch jax device state (smoke tests see 1 CPU device; only the dry-run
launcher sets XLA_FLAGS for 512 placeholder devices).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever this host actually has (tests / local runs)."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
