"""Config registry + shape cells for the assigned architectures.

Every architecture is selectable via ``--arch <id>``; ``reduced()`` derives
the small smoke-test variant of the same family; ``shape_cells()`` returns
the (shape-name, ShapeCell) pairs applicable to the arch (skips are
explicit, with reasons — see DESIGN.md §Arch-applicability).
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass
from typing import Dict, List, Optional

from repro.models import ModelConfig

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


@dataclass(frozen=True)
class ShapeCell:
    name: str                    # train_4k | prefill_32k | decode_32k | long_500k
    seq_len: int
    global_batch: int
    kind: str                    # train | prefill | decode
    skip_reason: Optional[str] = None

    @property
    def skipped(self) -> bool:
        return self.skip_reason is not None


SHAPES = {
    "train_4k": (4_096, 256, "train"),
    "prefill_32k": (32_768, 32, "prefill"),
    "decode_32k": (32_768, 128, "decode"),
    "long_500k": (524_288, 1, "decode"),
}

#: archs whose attention is full/quadratic with no sub-quadratic mode:
#: long_500k is skipped per the assignment.
_FULL_ATTENTION = {
    "stablelm_3b": "pure full attention (quadratic); long_500k skipped per assignment",
    "qwen2_7b": "pure full attention (quadratic); long_500k skipped per assignment",
    "granite_8b": "pure full attention (quadratic); long_500k skipped per assignment",
    "qwen2_moe_a2_7b": "pure full attention (quadratic); long_500k skipped per assignment",
    "qwen2_vl_2b": "pure full attention (quadratic); long_500k skipped per assignment",
    "whisper_large_v3": "enc-dec with 1500-frame encoder and 448-pos decoder; 500k ill-defined",
}


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


def shape_cells(arch: str) -> List[ShapeCell]:
    arch = arch.replace("-", "_")
    cells = []
    for name, (seq, batch, kind) in SHAPES.items():
        skip = None
        if name == "long_500k" and arch in _FULL_ATTENTION:
            skip = _FULL_ATTENTION[arch]
        cells.append(
            ShapeCell(name=name, seq_len=seq, global_batch=batch, kind=kind, skip_reason=skip)
        )
    return cells


def param_count(cfg: ModelConfig) -> int:
    """Approximate parameter count (exact for our param layout)."""
    D, L, V, F = cfg.d_model, cfg.n_layers, cfg.vocab, cfg.d_ff
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    total = V * D  # embed
    if not cfg.tie_embeddings and cfg.family in ("dense", "moe", "vlm"):
        total += D * V
    if cfg.family in ("dense", "moe", "vlm"):
        per = D * Hq * Dh + 2 * D * Hkv * Dh + Hq * Dh * D + 2 * D
        if cfg.qkv_bias:
            per += Hq * Dh + 2 * Hkv * Dh
        if cfg.moe is None:
            per += 3 * D * F
        else:
            m = cfg.moe
            per += D * m.n_experts + 3 * m.n_experts * D * m.d_ff_expert
            if m.n_shared:
                per += 3 * D * m.d_ff_shared + (D if m.shared_gate else 0)
        total += L * per
    elif cfg.family in ("ssm", "hybrid"):
        from repro.models.mamba2 import D_CONV, mamba_dims

        G, H = cfg.ssm_groups, cfg.ssm_heads
        d_inner, conv_dim = mamba_dims(D, H, cfg.ssm_head_dim, cfg.ssm_state, G)
        proj = 2 * d_inner + 2 * G * cfg.ssm_state + H
        per = D * proj + (D_CONV + 1) * conv_dim + 3 * H + d_inner * D + d_inner + D
        total += L * per
    if cfg.family == "hybrid":
        # shared blocks over [h ; e] (2D wide), and each application's
        # adapter and output linear
        r = cfg.adapter_rank
        block = 2 * D + 2 * D * Dh * (Hq + 2 * Hkv) + Hq * Dh * D + D + 3 * D * F
        app = r * (D + 2 * F) + D * D
        total += cfg.num_mem_blocks * block + len(cfg.hybrid_layer_ids) * app + D
    elif cfg.family == "audio":
        per_enc = D * Hq * Dh + 2 * D * Hkv * Dh + Hq * Dh * D + 3 * D * F
        per_dec = per_enc + D * Hq * Dh + 2 * D * Hkv * Dh + Hq * Dh * D
        total += L * (per_enc + per_dec) + D * D
    return total


#: active-parameter count for MoE (MODEL_FLOPS uses N_active)
def active_param_count(cfg: ModelConfig) -> int:
    if cfg.moe is None:
        return param_count(cfg)
    m = cfg.moe
    D, L = cfg.d_model, cfg.n_layers
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    total = cfg.vocab * D
    if not cfg.tie_embeddings:
        total += D * cfg.vocab
    per = D * Hq * Dh + 2 * D * Hkv * Dh + Hq * Dh * D
    per += D * m.n_experts + 3 * m.top_k * D * m.d_ff_expert
    if m.n_shared:
        per += 3 * D * m.d_ff_shared
    return total + L * per
