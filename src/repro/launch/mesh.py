"""Production meshes.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state.  The dry-run launcher force-hosts 512
placeholder devices *before* any jax import; everything else sees the real
device count.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape: tuple, axes: tuple):
    """`jax.make_mesh` with Auto axes: the sharding rules here place arrays
    with `with_sharding_constraint` / NamedSharding and let GSPMD propagate,
    which Explicit axes (make_mesh's default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU training)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return _auto_mesh((data, model), ("data", "model"))
