"""Config registry for the assigned architectures.

Every architecture is selectable via ``--arch <id>``: ``get_config()``
returns its published configuration and ``reduced_config()`` the small
smoke-test variant of the same family.
"""

from __future__ import annotations

import importlib
import math
from typing import Dict

import jax

from repro.models import Model, ModelConfig

ARCH_IDS = [
    "stablelm_3b",
    "gemma3_1b",
    "qwen2_7b",
    "granite_8b",
    "qwen2_moe_a2_7b",
    "llama4_scout_17b_a16e",
    "qwen2_vl_2b",
    "whisper_large_v3",
    "mamba2_370m",
    "zamba2_2_7b",
]


def get_config(arch: str) -> ModelConfig:
    arch = arch.replace("-", "_")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.CONFIG


def reduced_config(arch: str) -> ModelConfig:
    arch = arch.replace("-", "_")
    mod = importlib.import_module(f"repro.configs.{arch}")
    return mod.REDUCED


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}


def param_count(cfg: ModelConfig) -> int:
    """The number of parameters ``Model(cfg).init`` makes, counted from
    their shapes (:meth:`Model.abstract_init`), so nothing is allocated."""
    params, _ = Model(cfg).abstract_init()
    return sum(math.prod(x.shape) for x in jax.tree.leaves(params))
