"""Whisper-style encoder-decoder backbone (arXiv:2212.04356).

The audio frontend is a stub: the batch carries precomputed frame
embeddings (the output of the two-conv mel frontend), so
the encoder here is the transformer stack over frames; the decoder is a
standard causal transformer with cross-attention into the encoder output.

Whisper uses learned positions + pre-norm LayerNorm; we keep RMSNorm for
uniformity with the rest of the framework (backbone compute and shapes
identical).
"""

from __future__ import annotations

import math
from typing import Any, Tuple

import jax
import jax.numpy as jnp

from .attention import attention
from .common import rms_norm, swiglu, trunc_normal

Pytree = Any


def init_params(cfg, key: jax.Array) -> Tuple[Pytree, Pytree]:
    """cfg: ModelConfig with n_layers = encoder layers = decoder layers."""
    ks = jax.random.split(key, 12)
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab
    Hq, Hkv, Dh, F = cfg.n_heads, cfg.n_kv_heads, cfg.dh, cfg.d_ff
    dt = cfg.dtype

    def stack(k, d_in, d_out):
        init = lambda kk: trunc_normal(kk, (d_in, d_out), std=1.0 / math.sqrt(d_in), dtype=dt)
        return jax.vmap(init)(jax.random.split(k, L))

    def zstack(width):
        return jnp.zeros((L, width), dt)

    enc = {
        "ln1": zstack(D), "wq": stack(ks[0], D, Hq * Dh), "wk": stack(ks[1], D, Hkv * Dh),
        "wv": stack(ks[2], D, Hkv * Dh), "wo": stack(ks[3], Hq * Dh, D),
        "ln2": zstack(D), "w_gate": stack(ks[4], D, F), "w_up": stack(ks[5], D, F),
        "w_down": stack(ks[6], F, D),
    }
    dec = {
        "ln1": zstack(D), "wq": stack(ks[7], D, Hq * Dh), "wk": stack(ks[8], D, Hkv * Dh),
        "wv": stack(ks[9], D, Hkv * Dh), "wo": stack(ks[10], Hq * Dh, D),
        # cross attention
        "lnx": zstack(D), "xq": stack(ks[11], D, Hq * Dh), "xk": stack(ks[0], D, Hkv * Dh),
        "xv": stack(ks[1], D, Hkv * Dh), "xo": stack(ks[2], Hq * Dh, D),
        "ln2": zstack(D), "w_gate": stack(ks[3], D, F), "w_up": stack(ks[4], D, F),
        "w_down": stack(ks[5], F, D),
    }
    params = {
        "frame_proj": trunc_normal(ks[6], (D, D), std=1.0 / math.sqrt(D), dtype=dt),
        "enc": enc,
        "enc_ln": jnp.zeros((D,), dt),
        "embed": trunc_normal(ks[7], (V, D), std=0.02, dtype=dt),
        "dec": dec,
        "final_ln": jnp.zeros((D,), dt),
    }

    def axes_like(tree, table):
        return {k: table[k] for k in tree}

    mat2 = {
        "ln1": ("layers", "embed"), "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "heads"), "wv": ("layers", "embed", "heads"),
        "wo": ("layers", "heads", "embed"), "ln2": ("layers", "embed"),
        "w_gate": ("layers", "embed", "ff"), "w_up": ("layers", "embed", "ff"),
        "w_down": ("layers", "ff", "embed"),
        "lnx": ("layers", "embed"), "xq": ("layers", "embed", "heads"),
        "xk": ("layers", "embed", "heads"), "xv": ("layers", "embed", "heads"),
        "xo": ("layers", "heads", "embed"),
    }
    axes = {
        "frame_proj": ("embed", "embed2"),
        "enc": axes_like(enc, mat2),
        "enc_ln": ("embed",),
        "embed": ("vocab", "embed_tbl"),
        "dec": axes_like(dec, mat2),
        "final_ln": ("embed",),
    }
    return params, axes


def _self_block(cfg, lp, h, positions, attn_impl, causal, kv_cache=None, cache_positions=None):
    B, S, D = h.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    x = rms_norm(h, lp["ln1"])
    q = (x @ lp["wq"]).reshape(B, S, Hq, Dh)
    k = (x @ lp["wk"]).reshape(B, S, Hkv, Dh)
    v = (x @ lp["wv"]).reshape(B, S, Hkv, Dh)
    new_cache = None
    if kv_cache is not None:
        ck, cv = kv_cache
        upd = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (p, 0, 0)))
        ck = upd(ck, k.astype(ck.dtype), positions[:, 0])
        cv = upd(cv, v.astype(cv.dtype), positions[:, 0])
        k, v, kv_pos = ck, cv, cache_positions
        new_cache = (ck, cv)
    else:
        kv_pos = positions
    if causal:
        o = attention(q, k, v, positions, kv_pos, impl=attn_impl)
    else:
        # bidirectional encoder: every query position sees all keys
        o = attention(q, k, v, jnp.full_like(positions, kv_pos.shape[1]), kv_pos, impl=attn_impl)
    h = h + (o.reshape(B, S, -1) @ lp["wo"]).astype(h.dtype)
    x = rms_norm(h, lp["ln2"])
    h = h + (swiglu(x @ lp["w_gate"], x @ lp["w_up"]) @ lp["w_down"]).astype(h.dtype)
    return h, new_cache


def encode(cfg, params, frame_embeds: jax.Array, attn_impl: str = "chunked") -> jax.Array:
    """frame_embeds: (B, T, D) precomputed (conv frontend stub)."""
    h = (frame_embeds.astype(cfg.dtype) @ params["frame_proj"]).astype(cfg.dtype)
    B, T, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    def body(carry, lp):
        hh, _ = _self_block(cfg, lp, carry, positions, attn_impl, causal=False)
        return hh, None

    h, _ = jax.lax.scan(body, h, params["enc"])
    return rms_norm(h, params["enc_ln"])


def decode_train(
    cfg, params, enc_out: jax.Array, tokens: jax.Array, attn_impl: str = "chunked",
    remat: str = "none",
) -> jax.Array:
    """Teacher-forced decoder pass; returns final hidden states."""
    B, S = tokens.shape
    h = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    T = enc_out.shape[1]
    enc_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh

    def body(carry, lp):
        h = carry
        h, _ = _self_block(cfg, lp, h, positions, attn_impl, causal=True)
        # cross attention
        x = rms_norm(h, lp["lnx"])
        q = (x @ lp["xq"]).reshape(B, S, Hq, Dh)
        k = (enc_out @ lp["xk"]).reshape(B, T, Hkv, Dh)
        v = (enc_out @ lp["xv"]).reshape(B, T, Hkv, Dh)
        # bidirectional over encoder frames: q_position >= all kv positions
        o = attention(q, k, v, jnp.full((B, S), T, jnp.int32), enc_pos, impl=attn_impl)
        h = h + (o.reshape(B, S, -1) @ lp["xo"]).astype(h.dtype)
        return h, None

    fn = body
    if remat in ("dots", "full"):
        fn = jax.checkpoint(body, prevent_cse=False)
    h, _ = jax.lax.scan(fn, h, params["dec"])
    return rms_norm(h, params["final_ln"])


def decode_step(
    cfg, params, enc_out: jax.Array, tokens: jax.Array, positions: jax.Array,
    kv_caches: Tuple[jax.Array, jax.Array], cache_positions: jax.Array,
    attn_impl: str = "chunked",
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Single-token decoder step with self-attention KV cache."""
    B, S = tokens.shape
    h = params["embed"][tokens].astype(cfg.dtype)
    T = enc_out.shape[1]
    enc_pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh

    def body(carry, xs):
        h = carry
        lp, ck, cv = xs
        h, new_cache = _self_block(
            cfg, lp, h, positions, "chunked", causal=True,
            kv_cache=(ck, cv), cache_positions=cache_positions,
        )
        x = rms_norm(h, lp["lnx"])
        q = (x @ lp["xq"]).reshape(B, S, Hq, Dh)
        k = (enc_out @ lp["xk"]).reshape(B, T, Hkv, Dh)
        v = (enc_out @ lp["xv"]).reshape(B, T, Hkv, Dh)
        o = attention(q, k, v, jnp.full((B, S), T, jnp.int32), enc_pos, impl=attn_impl)
        h = h + (o.reshape(B, S, -1) @ lp["xo"]).astype(h.dtype)
        return h, new_cache

    h, new_caches = jax.lax.scan(body, h, (params["dec"],) + kv_caches)
    return rms_norm(h, params["final_ln"]), new_caches
