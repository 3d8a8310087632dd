"""Mesh construction.

Every axis is ``AxisType.Auto``: the sharding rules in :mod:`repro.sharding`
are GSPMD rules (the compiler propagates shardings through the step), while
``jax.make_mesh`` defaults to ``Explicit`` axes, under which an ordinary
gather such as ``embed[tokens]`` from a vocab-sharded table is a type error.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def make_host_mesh(model: int = 1, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A ``(data, model)`` mesh over ``devices`` (default: every device there is)."""
    devices = list(devices if devices is not None else jax.devices())
    model = min(model, len(devices))
    return jax.make_mesh((len(devices) // model, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2, devices=devices)
