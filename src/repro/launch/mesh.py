"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16).
Multi-pod:  2 pods x 256 chips as (pod=2, data=16, model=16); the ``pod``
axis extends data parallelism across the inter-pod links (one gradient
all-reduce crosses it per step).

Every axis is ``AxisType.Auto``: the sharding rules in :mod:`repro.sharding`
are GSPMD rules (the compiler propagates shardings through the step), while
``jax.make_mesh`` defaults to ``Explicit`` axes, under which an ordinary
gather such as ``embed[tokens]`` from a vocab-sharded table is a type error.

``make_production_mesh`` is a function (never module-level state) so that
importing this module never touches JAX device state — only the dry-run
entry point forces the 512-device host platform.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes, devices=None) -> Mesh:
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A small mesh over ``devices`` (default: every device there is)."""
    devices = list(devices if devices is not None else jax.devices())
    model = min(model, len(devices))
    return _auto_mesh((len(devices) // model, model), ("data", "model"), devices)
