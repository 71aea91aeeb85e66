"""Production meshes. Defined as FUNCTIONS so importing this module never
touches jax device state (the dry-run must set XLA_FLAGS before first init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod.

    Axes: data (FSDP/batch), model (TP/expert). The multi-pod mesh adds a
    leading pure-DP "pod" axis — parameters are never sharded across it, so
    pods can join/leave elastically (see runtime/elastic.py)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh():
    """Whatever devices exist (CPU smoke tests: 1 device)."""
    n = len(jax.devices())
    return jax.make_mesh((1, n), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
