"""Plain reference for the decoder-only configurations the benchmark serves.

It imports nothing of the program and takes nothing the program made.  The
weights are made anew from the seed by the initialization the program
documents (``models/transformer.py:init_params``): truncated normals at
±2 std, ``1/sqrt(d_in)`` for every projection, 0.02 for the embedding,
zero norm scales, one key per matrix in the order listed in
:func:`make_weights`.  They are kept in the type they are served in
(bfloat16) and every matmul upcasts them to float32 at ``HIGHEST``
precision, layer by layer, so the reference fits beside nothing else on one
chip.

The layer, as the configuration file states it: pre-norm RMSNorm with a
unit offset (``x * rsqrt(mean(x^2) + eps) * (1 + scale)``), rotary position
embedding over the full head dimension (rotate-half pairing), causal
grouped-query attention scaled by ``1/sqrt(head_dim)``, a SwiGLU MLP, a
final RMSNorm and an untied output head.

``precision="fp8"`` is the control: the same computation with every weight
and every matmul input rounded to float8 e4m3 (weights scaled per output
column, activations per row), the step below bfloat16 a later change might
take to serve faster.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool

    @classmethod
    def from_config(cls, model: Dict) -> "Dims":
        """``model`` holds the configuration file's keys (Hugging Face names);
        ``program_layer`` states the layer as run where it departs from them."""
        d, h = int(model["hidden_size"]), int(model["num_attention_heads"])
        return cls(
            layers=int(model["num_hidden_layers"]),
            d_model=d,
            heads=h,
            kv_heads=int(model["num_key_value_heads"]),
            head_dim=int(model.get("head_dim") or d // h),
            d_ff=int(model["intermediate_size"]),
            vocab=int(model["vocab_size"]),
            rope_theta=float(model["rope_theta"]),
            norm_eps=float(model["program_layer"]["norm_eps"]),
            tie_embeddings=bool(model.get("tie_word_embeddings", False)),
        )


def make_weights(m: Dims, key: jax.Array, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """The served weights for ``key``; layer matrices stacked on axis 0."""
    keys = jax.random.split(key, 16)

    def normal(k, shape, std):
        x = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * std
        return x.astype(dtype)

    def stacked(k, d_in, d_out):
        per_layer = jax.random.split(k, m.layers)
        return jax.vmap(lambda kk: normal(kk, (d_in, d_out), 1.0 / math.sqrt(d_in)))(per_layer)

    q_width, kv_width = m.heads * m.head_dim, m.kv_heads * m.head_dim
    w = {
        "embed": normal(keys[0], (m.vocab, m.d_model), 0.02),
        "ln1": jnp.zeros((m.layers, m.d_model), dtype),
        "ln2": jnp.zeros((m.layers, m.d_model), dtype),
        "wq": stacked(keys[3], m.d_model, q_width),
        "wk": stacked(keys[4], m.d_model, kv_width),
        "wv": stacked(keys[5], m.d_model, kv_width),
        "wo": stacked(keys[6], q_width, m.d_model),
        "w_gate": stacked(keys[8], m.d_model, m.d_ff),
        "w_up": stacked(keys[9], m.d_model, m.d_ff),
        "w_down": stacked(keys[10], m.d_ff, m.d_model),
        "final_ln": jnp.zeros((m.d_model,), dtype),
    }
    if not m.tie_embeddings:
        w["head"] = normal(keys[7], (m.d_model, m.vocab), 1.0 / math.sqrt(m.d_model))
    return w


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round ``x`` to float8 e4m3, scaled so each slice along ``axis`` spans it."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _matmul(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, Dh); rotate-half pairing over the whole head."""
    dh = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = positions[:, :, None].astype(jnp.float32) * inv_freq
    sin, cos = jnp.sin(angles)[:, :, None, :], jnp.cos(angles)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(m: Dims, precision: str, h: jax.Array, lw: Dict[str, jax.Array]) -> jax.Array:
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = _rms(h, lw["ln1"], m.norm_eps)
    q = _matmul(x, lw["wq"], precision).reshape(b, s, m.heads, m.head_dim)
    k = _matmul(x, lw["wk"], precision).reshape(b, s, m.kv_heads, m.head_dim)
    v = _matmul(x, lw["wv"], precision).reshape(b, s, m.kv_heads, m.head_dim)
    q, k = _rope(q, positions, m.rope_theta), _rope(k, positions, m.rope_theta)
    group = m.heads // m.kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / math.sqrt(m.head_dim)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST).reshape(b, s, -1)
    h = h + _matmul(o, lw["wo"], precision)
    x = _rms(h, lw["ln2"], m.norm_eps)
    gate = _matmul(x, lw["w_gate"], precision)
    up = _matmul(x, lw["w_up"], precision)
    return h + _matmul(jax.nn.silu(gate) * up, lw["w_down"], precision)


_LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def logits_at(
    m: Dims, w: Dict[str, jax.Array], tokens: jax.Array, at: jax.Array, precision: str = "f32"
) -> jax.Array:
    """Logits (B, n, V) in float32 at positions ``at`` (B, n) of ``tokens`` (B, S).

    Attention is causal, so padding after a row's last read position
    changes nothing that is read.
    """
    h = w["embed"][tokens].astype(jnp.float32)
    layers = {k: w[k] for k in _LAYER_KEYS}
    h, _ = jax.lax.scan(lambda c, lw: (_layer(m, precision, c, lw), None), h, layers)
    h = jnp.take_along_axis(h, at[:, :, None], axis=1)
    h = _rms(h, w["final_ln"], m.norm_eps)
    head = w["embed"].T if m.tie_embeddings else w["head"]
    return _matmul(h, head, precision)
