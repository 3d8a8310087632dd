"""Shared model components: norms, rotary embeddings, initializers.

Everything is a plain function over pytrees of ``jnp`` arrays — no Flax/NNX
dependency — so that parameter sharding stays a pure metadata concern
(:mod:`repro.sharding`) and layer stacks can be ``jax.lax.scan``-ed with
O(1) HLO size in depth.
"""

from __future__ import annotations

import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

Pytree = Any

# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def trunc_normal(key: jax.Array, shape, std: float, dtype=jnp.float32) -> jax.Array:
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)


def dense_init(key: jax.Array, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    return trunc_normal(key, (d_in, d_out), std=1.0 / math.sqrt(d_in), dtype=dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings (standard, and the M-RoPE hook for qwen2-vl)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float = 10_000.0) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)  # (head_dim/2,)


def apply_rope(
    x: jax.Array,  # (B, S, H, Dh)
    positions: jax.Array,  # (B, S) int32
    theta: float = 10_000.0,
) -> jax.Array:
    freqs = rope_frequencies(x.shape[-1], theta)  # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, Dh/2)
    sin = jnp.sin(angles)[:, :, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,  # (B, S, H, Dh)
    positions: jax.Array,  # (B, S, 3) int32 — temporal / height / width
    theta: float = 1_000_000.0,
    sections: Tuple[int, int, int] = (2, 3, 3),  # qwen2-vl mrope_section /8ths
) -> jax.Array:
    """Multimodal RoPE (qwen2-vl): the head dim is partitioned into three
    frequency sections, each rotated by its own position stream."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)  # (Dh/2,)
    n = dh // 2
    total = sum(sections)
    bounds = []
    acc = 0
    for s in sections:
        acc += round(n * s / total)
        bounds.append(acc)
    bounds[-1] = n
    sec_id = jnp.zeros((n,), jnp.int32)
    sec_id = jnp.where(jnp.arange(n) >= bounds[0], 1, sec_id)
    sec_id = jnp.where(jnp.arange(n) >= bounds[1], 2, sec_id)
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32),  # (B, S, 3)
        jnp.broadcast_to(sec_id[None, None, :], positions.shape[:2] + (n,)).astype(jnp.int32) ,
        axis=-1,
    )  # (B, S, n): per-frequency position stream
    angles = pos * freqs  # (B, S, n)
    sin = jnp.sin(angles)[:, :, None, :]
    cos = jnp.cos(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)


# ---------------------------------------------------------------------------
# Attention masks
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def causal_mask_bias(
    q_positions: jax.Array,  # (B, Sq)
    kv_positions: jax.Array,  # (B, Skv)
    window: Optional[int] = None,
    chunk: Optional[int] = None,
) -> jax.Array:
    """(B, 1, Sq, Skv) additive bias: causal, optionally sliding-window
    (gemma3 local layers) or chunked (llama4 iRoPE chunked attention)."""
    q = q_positions[:, None, :, None]
    k = kv_positions[:, None, None, :]
    ok = k <= q
    if window is not None:
        ok = jnp.logical_and(ok, k > q - window)
    if chunk is not None:
        ok = jnp.logical_and(ok, (k // chunk) == (q // chunk))
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)
