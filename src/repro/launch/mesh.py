"""Production mesh construction (functions only — importing this module
never touches jax device state)."""

from __future__ import annotations

from typing import Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    """A mesh whose axes are all ``Auto`` (GSPMD propagates shardings)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


def data_axes_of(mesh) -> Tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def n_chips(mesh) -> int:
    n = 1
    for s in mesh.devices.shape:
        n *= s
    return n
