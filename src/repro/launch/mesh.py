"""Production mesh construction (pure function — importing this module never
touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod (data, model); 2x16x16 = 512 chips multi-pod.

    The ``pod`` axis joins ICI-connected slices over DCN and is used only for
    data parallelism / hierarchical gradient reduction, so DCN latency hides
    behind per-layer compute."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_mesh(shape, axes):
    """Arbitrary logical meshes for tests / elastic restarts."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))
