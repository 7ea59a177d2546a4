"""Production mesh builders.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state.  The single-pod production mesh is a 16x16 = 256
chip pod ("data", "model"); the multi-pod mesh is 2 pods = 512 chips
("pod", "data", "model") where the "pod" axis crosses the (slow) DCN.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the sharding rules place arrays
    with ``with_sharding_constraint``, which refers only to Auto axes
    (``make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — used by tests."""
    return _auto_mesh((data, model), ("data", "model"))
