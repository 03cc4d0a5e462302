"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so
importing this module never touches jax device state — the dry-run
must set XLA_FLAGS before the first jax call, and smoke tests must see
the real single device.
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str]):
    """``jax.make_mesh`` with every axis ``Auto``.

    The sharding rules constrain activations with
    ``with_sharding_constraint``, which refuses the ``Explicit`` axes
    ``jax.make_mesh`` builds by default.
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips).

    Axes: ``pod`` (DP across pods, slower ICI/DCN), ``data`` (DP/FSDP),
    ``model`` (TP/EP/SP).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many local devices exist (tests)."""
    return make_mesh((data, model), ("data", "model"))
