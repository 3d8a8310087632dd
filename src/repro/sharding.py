"""Logical-axis sharding rules (GSPMD / MaxText style).

Every parameter and activation carries a tuple of *logical* axis names; a
per-run rule table maps logical names to mesh axes, those of the
``("data", "model")`` meshes of :mod:`repro.launch.mesh`.

Default rule set:

* ``embed``/``ff``/``heads``/``vocab``   -> tensor parallel over ``model``
* ``layers``/norm scales                 -> replicated
* ``batch``                             -> data parallel over ``data``
* ``expert``                            -> expert parallel over ``model`` when
  the expert count divides the model axis; otherwise experts replicate and
  ``ff_expert`` takes the model axis (TP inside experts).
* optional FSDP: parameters additionally shard their ``embed``/``ff`` (dim0)
  axis over ``data`` (zero-3 style), controlled per run.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical axis -> mesh axis (or None=replicate, or tuple of mesh axes)."""

    table: Tuple[Tuple[str, Any], ...]

    def get(self, logical: Optional[str]):
        if logical is None:
            return None
        for name, mesh_ax in self.table:
            if name == logical:
                return mesh_ax
        return None

    def spec(self, axes: Optional[Tuple[Optional[str], ...]]) -> P:
        if axes is None:
            return P()
        return P(*(self.get(a) for a in axes))


def default_rules(
    mesh: Mesh,
    *,
    n_experts: int = 0,
    fsdp: bool = False,
    sequence_parallel: bool = False,
) -> ShardingRules:
    axes = mesh.axis_names
    model_ax = "model" if "model" in axes else None
    dp = "data" if "data" in axes else None
    model_size = mesh.shape.get("model", 1) if model_ax else 1

    expert_ax: Any = None
    ff_expert_ax: Any = model_ax
    if n_experts and model_ax and n_experts % model_size == 0:
        expert_ax, ff_expert_ax = model_ax, None  # clean EP

    table = [
        # parameters
        ("vocab", model_ax),
        ("embed", dp if fsdp else None),
        ("embed_tbl", None),  # vocab matrices: never FSDP the D dim
        ("embed2", None),
        ("heads", model_ax),
        ("ff", model_ax),
        ("expert", expert_ax),
        ("ff_expert", ff_expert_ax),
        ("expert_dim", None),
        ("layers", None),
        # activations
        ("batch", dp),
        ("seq", model_ax if sequence_parallel else None),
        ("kv_seq", None),
        ("head_dim", None),
        ("act_embed", None),
    ]
    return ShardingRules(table=tuple(table))


def _axis_size(mesh: Mesh, mesh_ax) -> int:
    if mesh_ax is None:
        return 1
    if isinstance(mesh_ax, tuple):
        return int(np.prod([mesh.shape[a] for a in mesh_ax]))
    return mesh.shape[mesh_ax]


def logical_to_sharding(
    axes_tree: Pytree, mesh: Mesh, rules: ShardingRules, like: Optional[Pytree] = None
) -> Pytree:
    """Map a logical-axes pytree (tuples are leaves) to NamedShardings.

    When ``like`` (a matching pytree of arrays/ShapeDtypeStructs) is given,
    any dimension not divisible by its assigned mesh axes is replicated
    instead — e.g. whisper's vocab 51866 and mamba2's 50280 do not divide
    the 16-way model axis, so their embedding tables replicate (explicit
    pjit shardings require exact divisibility)."""

    is_axes_leaf = lambda x: isinstance(x, tuple) and all(
        a is None or isinstance(a, str) for a in x
    )

    def to_sharding(axes, leaf=None):
        mesh_axes = [rules.get(a) for a in axes]
        if leaf is not None:
            shape = leaf.shape
            mesh_axes = [
                ax if ax is None or d % _axis_size(mesh, ax) == 0 else None
                for d, ax in zip(shape, mesh_axes)
            ]
        return NamedSharding(mesh, P(*mesh_axes))

    if like is None:
        return jax.tree.map(to_sharding, axes_tree, is_leaf=is_axes_leaf)
    return jax.tree.map(to_sharding, axes_tree, like, is_leaf=is_axes_leaf)

