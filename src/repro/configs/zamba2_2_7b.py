"""zamba2-2.7b [hybrid]: 54L d_model=2560 32H (kv=32) d_ff=10240 vocab=32000.

Mamba2 backbone (d_state 64, d_inner 5120, head_dim 64 -> 80 SSM heads)
fed by weight-SHARED attention blocks over [h ; e] (the Zamba2 layer of
:mod:`repro.models.hybrid`). [arXiv:2411.15242; hf]

The widths above are the model's.  Its ``config.json`` is not at hand, so
these follow the family's convention (Zamba2-7B's config) and are not read
from it: 2 memory blocks used in turn, an application every 6 layers from
layer 6, heads of 2·D/H = 160, an MLP adapter of rank 128, 1 group of B/C.
"""

import dataclasses

from repro.models import ModelConfig

CONFIG = ModelConfig(
    name="zamba2_2_7b",
    family="hybrid",
    n_layers=54,
    d_model=2560,
    n_heads=32,
    n_kv_heads=32,
    head_dim=160,
    d_ff=10240,
    vocab=32_000,
    tie_embeddings=True,
    ssm_state=64,
    ssm_heads=80,
    ssm_head_dim=64,
    ssm_chunk=256,
    hybrid_layer_ids=tuple(range(6, 54, 6)),
    num_mem_blocks=2,
    adapter_rank=128,
    notes=(
        "Mamba2 + 2 shared attn blocks over [h; e], 8 applications each with "
        "its own adapter and KV cache; long_500k RUNS (SSM decode O(1), attn "
        "decode O(S) reads)"
    ),
)

REDUCED = dataclasses.replace(
    CONFIG, name="zamba2_smoke", n_layers=6, d_model=64, n_heads=4,
    n_kv_heads=4, head_dim=32, d_ff=128, vocab=256, ssm_state=16, ssm_heads=8,
    ssm_head_dim=16, ssm_chunk=16, hybrid_layer_ids=(2, 4), adapter_rank=4,
)
