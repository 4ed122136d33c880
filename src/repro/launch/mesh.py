"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (smoke tests keep their single CPU device).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """(16, 16) data x model single pod (256 chips, v5e), or
    (2, 16, 16) pod x data x model for the 512-chip two-pod dry-run."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(n_data: int = 1, n_model: int = 1):
    """Small mesh over however many (virtual) devices exist — tests."""
    return _make_mesh((n_data, n_model), ("data", "model"))


def make_lane_mesh(devices=None, *, axis: str = "data"):
    """1-D mesh over ``devices`` (default: every attached device) for the
    lane-sharded fused engine (:mod:`repro.core.sharded_lanes`).

    Built from an explicit device list — unlike :func:`jax.make_mesh` this
    lets tests and benchmarks pin a subset (e.g. half the forced host
    devices) without touching global state."""
    import numpy as np
    from jax.sharding import Mesh
    devs = list(jax.devices()) if devices is None else list(devices)
    return Mesh(np.array(devs), (axis,))
