"""Attention: GQA with three interchangeable inner implementations.

* ``xla``      plain softmax(QK^T)V — materializes (Sq, Skv) scores; fine for
               short sequences, used as the semantic reference.
* ``chunked``  online-softmax over KV chunks via ``jax.lax.scan`` — the
               *register-demotion adapted* formulation: the running
               (m, l, acc) statistics stay in the scan carry (registers /
               VMEM once compiled) instead of materializing scores to HBM.
               Memory O(Sq x chunk), required for the 32k/500k shape cells.
* ``pallas``   the TPU kernel (:mod:`repro.kernels.flash_attention`), same
               math with explicit VMEM scratch residency.

All paths share the GQA head-grouping and mask conventions and are tested
allclose against each other.

Decoding one token per sequence against a KV cache takes
:func:`attention_decode_at` whatever the ``impl``: the shape chooses it, and
the platform chooses between the Pallas kernel
(:mod:`repro.kernels.decode_attention`) on a TPU and :func:`attention_decode`
elsewhere.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from . import scopes
from .common import scan as common_scan, NEG_INF, causal_mask_bias

DEFAULT_CHUNK = 1024


def _expand_kv(k: jax.Array, n_q_heads: int) -> jax.Array:
    """(B, S, Hkv, Dh) -> (B, S, Hq, Dh) by group broadcast."""
    b, s, hkv, dh = k.shape
    groups = n_q_heads // hkv
    if groups == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, hkv, groups, dh))
    return k.reshape(b, s, n_q_heads, dh)


def attention_xla(
    q: jax.Array,  # (B, Sq, Hq, Dh)
    k: jax.Array,  # (B, Skv, Hkv, Dh)
    v: jax.Array,  # (B, Skv, Hkv, Dh)
    bias: Optional[jax.Array] = None,  # (B, 1, Sq, Skv) additive
    scale: Optional[float] = None,
) -> jax.Array:
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    k = _expand_kv(k, q.shape[2])
    v = _expand_kv(v, q.shape[2])
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        logits = logits + bias
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,  # (B, Sq)
    kv_positions: jax.Array,  # (B, Skv)
    window: Optional[int] = None,
    chunk_attn: Optional[int] = None,
    scale: Optional[float] = None,
    kv_chunk: int = DEFAULT_CHUNK,
) -> jax.Array:
    """Online-softmax attention, scanning KV in chunks.

    The (m, l, acc) running statistics live in the scan carry — the JAX-level
    analogue of RegDem's demoted registers: state that would otherwise be
    spilled to HBM as (Sq x Skv) score tiles stays resident across the
    chunk loop.  FLOPs are identical to ``attention_xla``; peak memory is
    O(Sq x kv_chunk) per head.
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, sq, hq, dh = q.shape
    skv = k.shape[1]
    k = _expand_kv(k, hq)
    v = _expand_kv(v, hq)
    n_chunks = -(-skv // kv_chunk)
    pad = n_chunks * kv_chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad)), constant_values=-1)
    kc = k.reshape(b, n_chunks, kv_chunk, hq, dh).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, kv_chunk, hq, dh).transpose(1, 0, 2, 3, 4)
    pc = kv_positions.reshape(b, n_chunks, kv_chunk).transpose(1, 0, 2)

    qf = q.astype(jnp.float32)

    def step(carry, xs):
        m, l, acc = carry  # (B,H,Sq), (B,H,Sq), (B,Sq,H,Dh)
        kci, vci, pci = xs
        logits = jnp.einsum("bqhd,bkhd->bhqk", qf, kci.astype(jnp.float32)) * scale
        valid = pci[:, None, None, :] >= 0
        ok = jnp.logical_and(valid, pci[:, None, None, :] <= q_positions[:, None, :, None])
        if window is not None:
            ok = jnp.logical_and(
                ok, pci[:, None, None, :] > q_positions[:, None, :, None] - window
            )
        if chunk_attn is not None:
            ok = jnp.logical_and(
                ok,
                (pci[:, None, None, :] // chunk_attn)
                == (q_positions[:, None, :, None] // chunk_attn),
            )
        logits = jnp.where(ok, logits, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vci.astype(jnp.float32))
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    a0 = jnp.zeros((b, sq, hq, dh), jnp.float32)
    (m, l, acc), _ = common_scan(step, (m0, l0, a0), (kc, vc, pc))
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def attention_decode(
    q: jax.Array,  # (B, 1, Hq, Dh): one query token per sequence
    k: jax.Array,  # (B, 1, Hkv, Dh): that token's own key
    v: jax.Array,  # (B, 1, Hkv, Dh)
    k_cache: jax.Array,  # (B, Skv, Hkv, Dh)
    v_cache: jax.Array,  # (B, Skv, Hkv, Dh)
    positions: jax.Array,  # (B,) each token's position
    window: Optional[jax.Array] = None,
    chunk_attn: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """One token per sequence against its cache, with the cache read where
    it is stored.

    Row ``b`` attends to the cache positions strictly before
    ``positions[b]`` (under the window and chunk masks) and to its own
    ``k``/``v``, in one float32 softmax; what the cache holds at and after
    ``positions[b]`` takes no part.  Scores and the weighted sum are float32
    multiply-and-reduce over ``Dh`` and over positions, and query heads are
    grouped over their shared KV head: no dot operand, reshape or GQA
    broadcast of the cache whose layout could differ from the cache's own,
    so the compiler has no reason to copy a cache slice.  -> (B, 1, Hq, Dh)
    """
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, _, hq, dh = q.shape
    skv, hkv = k_cache.shape[1], k_cache.shape[2]
    qf = q.reshape(b, hkv, hq // hkv, dh).astype(jnp.float32)  # (B, Hkv, G, Dh)

    t = jnp.arange(skv, dtype=positions.dtype)[None, :]
    pos = positions[:, None]
    ok = t < pos
    if window is not None:
        ok = jnp.logical_and(ok, t > pos - window)
    if chunk_attn is not None:
        ok = jnp.logical_and(ok, t // chunk_attn == pos // chunk_attn)
    ok = ok[:, :, None, None]  # (B, Skv, 1, 1)

    kf = k_cache[:, :, :, None, :].astype(jnp.float32)  # (B, Skv, Hkv, 1, Dh)
    logits = jnp.sum(qf[:, None] * kf, axis=-1) * scale  # (B, Skv, Hkv, G)
    own = jnp.sum(qf * k[:, 0, :, None, :].astype(jnp.float32), axis=-1) * scale
    m = jnp.maximum(jnp.max(jnp.where(ok, logits, NEG_INF), axis=1), own)  # (B, Hkv, G)
    p = jnp.where(ok, jnp.exp(logits - m[:, None]), 0.0)
    p_own = jnp.exp(own - m)
    l = jnp.sum(p, axis=1) + p_own
    vf = v_cache[:, :, :, None, :].astype(jnp.float32)
    acc = jnp.sum(p[..., None] * vf, axis=1)  # (B, Hkv, G, Dh)
    acc = acc + p_own[..., None] * v[:, 0, :, None, :].astype(jnp.float32)
    out = acc / l[..., None]
    return out.reshape(b, 1, hq, dh).astype(q.dtype)


def attention_decode_at(
    q: jax.Array,  # (B, 1, Hq, Dh): one query token per sequence
    k: jax.Array,  # (B, 1, Hkv, Dh): that token's own key
    v: jax.Array,  # (B, 1, Hkv, Dh)
    k_all: jax.Array,  # (N, B, Skv, Hkv, Dh): stacked caches
    v_all: jax.Array,  # (N, B, Skv, Hkv, Dh)
    index: jax.Array,  # () int: the entry of the stack to attend to
    positions: jax.Array,  # (B,) each token's position
    window: Optional[jax.Array] = None,
    chunk_attn: Optional[jax.Array] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """:func:`attention_decode` against entry ``index`` of stacked caches:
    on a TPU :func:`attention_decode_kernel`; elsewhere the entry's slices
    (scope ``kv_carry``) go to :func:`attention_decode`.  -> (B, 1, Hq, Dh)"""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])

    def reference():
        with jax.named_scope(scopes.KV_CARRY):
            k_cache, v_cache = k_all[index], v_all[index]
        return attention_decode(q, k, v, k_cache, v_cache, positions, window=window,
                                chunk_attn=chunk_attn, scale=scale)

    def kernel():
        return attention_decode_kernel(q, k, v, k_all, v_all, index, positions, window=window,
                                       chunk_attn=chunk_attn, scale=scale)

    return jax.lax.platform_dependent(tpu=kernel, default=reference)


def attention_decode_kernel(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    k_all: jax.Array,
    v_all: jax.Array,
    index: jax.Array,
    positions: jax.Array,
    window: Optional[jax.Array] = None,
    chunk_attn: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """:func:`attention_decode_at` by the Pallas kernel
    (:mod:`repro.kernels.decode_attention`), which takes the whole stack,
    read where it is stored: positions are the minor dimension of the
    caches' stored layout, so the kernel's view of them, ``(N, B, Hkv, Dh,
    Skv)``, is those bytes.  It reads each slot's positions in ``[lo,
    min(position, Skv))`` only, ``lo`` from the window and chunk masks; the
    token's own key and value are merged here in the same float32 softmax.
    ``interpret`` runs the kernel in Pallas's interpreter."""
    from repro.kernels.decode_attention import decode_attention

    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    b, _, hq, dh = q.shape
    hkv = k.shape[2]
    hi = jnp.minimum(positions, k_all.shape[2])
    lo = jnp.zeros_like(positions)
    if window is not None:
        lo = jnp.maximum(lo, positions - window + 1)
    if chunk_attn is not None:
        lo = jnp.maximum(lo, positions // chunk_attn * chunk_attn)
    stored = lambda c: jnp.moveaxis(c, 2, -1)
    m, l, acc = decode_attention(q[:, 0], stored(k_all), stored(v_all), index,
                                 jnp.minimum(lo, hi), hi, scale=scale, interpret=interpret)

    qf = q.reshape(b, hkv, hq // hkv, dh).astype(jnp.float32)
    own = jnp.sum(qf * k[:, 0, :, None, :].astype(jnp.float32), axis=-1) * scale
    m_cache = m.reshape(own.shape)
    m = jnp.maximum(m_cache, own)  # (B, Hkv, G)
    corr, p_own = jnp.exp(m_cache - m), jnp.exp(own - m)
    l = l.reshape(own.shape) * corr + p_own
    acc = (acc.reshape(qf.shape) * corr[..., None]
           + p_own[..., None] * v[:, 0, :, None, :].astype(jnp.float32))
    out = acc / l[..., None]
    return out.reshape(b, 1, hq, dh).astype(q.dtype)


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    impl: str = "xla",
    window: Optional[int] = None,
    chunk_attn: Optional[int] = None,
    kv_chunk: int = DEFAULT_CHUNK,
    scale: Optional[float] = None,
) -> jax.Array:
    """Unified entry point used by every architecture; ``scale`` defaults to
    ``1/sqrt(Dh)``."""
    if impl == "chunked":
        return attention_chunked(
            q, k, v, q_positions, kv_positions,
            window=window, chunk_attn=chunk_attn, scale=scale, kv_chunk=kv_chunk,
        )
    if impl == "pallas":
        from repro.kernels import ops as kernel_ops

        if scale is not None:
            raise NotImplementedError("the flash attention kernel scales by 1/sqrt(Dh)")

        return kernel_ops.flash_attention(
            q, k, v, q_positions, kv_positions, window=window, chunk_attn=chunk_attn
        )
    bias = causal_mask_bias(q_positions, kv_positions, window=window, chunk=chunk_attn)
    return attention_xla(q, k, v, bias=bias, scale=scale)
