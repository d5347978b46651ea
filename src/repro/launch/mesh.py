"""Production meshes.

Functions (not module constants) so importing never touches jax device
state; the dry-run sets XLA_FLAGS before any jax import.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(shape=(2, 4), axes=("data", "model")):
    """Small mesh over whatever devices exist (tests / host benchmarks)."""
    import numpy as np

    n = len(jax.devices())
    want = int(np.prod(shape))
    if want > n:
        shape = (1, n)
    return make_mesh(shape, axes)


from repro.core.engine import mesh_axes_dict  # noqa: E402  (re-export)
