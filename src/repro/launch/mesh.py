"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (required by the brief): tests see 1 CPU device;
only ``dryrun.py`` forces 512 host devices via XLA_FLAGS before any import.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 = 256 chips/pod; multi-pod: 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_debug_mesh(shape=(2, 4), axes=("data", "model")) -> Mesh:
    """Small mesh for tests (requires xla_force_host_platform_device_count
    set by the test itself)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))
