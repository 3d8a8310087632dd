"""zamba2-7b [hybrid]: 81L d_model=3584 32H (kv=32) d_ff=14336 vocab=32000.

Zyphra/Zamba2-7B-Instruct's ``config.json``: Mamba2 layers (d_state 64,
expand 2 -> d_inner 7168, head_dim 64 -> 112 SSM heads, 2 groups of B/C,
conv 4) and two shared attention blocks over [h ; e] (attention width 7168,
32 heads of 224), used in turn at the 13 layers of ``hybrid_layer_ids``,
each application with its own rank-128 MLP adapter and output linear; the
shared MLP is a gated erf GELU; tied embeddings (the family's default).
[arXiv:2411.15242; hf]
"""

import dataclasses

from repro.models import ModelConfig

CONFIG = ModelConfig(
    name="zamba2_7b",
    family="hybrid",
    n_layers=81,
    d_model=3584,
    n_heads=32,
    n_kv_heads=32,
    head_dim=224,
    d_ff=14336,
    vocab=32_000,
    tie_embeddings=True,
    rope_theta=10_000.0,
    ssm_state=64,
    ssm_heads=112,
    ssm_head_dim=64,
    ssm_chunk=256,
    ssm_groups=2,
    hybrid_layer_ids=(6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77),
    num_mem_blocks=2,
    adapter_rank=128,
    notes="Mamba2 + 2 shared attn blocks over [h; e], 13 applications",
)

REDUCED = dataclasses.replace(
    CONFIG, name="zamba2_7b_smoke", n_layers=9, d_model=64, n_heads=4,
    n_kv_heads=4, head_dim=32, d_ff=128, vocab=256, ssm_state=16, ssm_heads=8,
    ssm_head_dim=16, ssm_chunk=16, hybrid_layer_ids=(2, 4, 7), adapter_rank=4,
)
