"""Jitted public wrappers for the Pallas kernels.

``flash_attention`` adapts (B, S, H, Dh) model-layout operands (GQA grouping
included) onto the (batch*heads)-flattened kernel; ``mamba2_ssd`` wraps the
chunked SSD kernel.  With ``interpret=None`` the wrappers run the kernels
in interpret mode only on the CPU backend; every other backend compiles
them.  Both modes share the same kernel body, which is what the shape/dtype
sweep tests validate against :mod:`repro.kernels.ref`.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import flash_attention as fa
from . import mamba2_ssd as ssd


def _interpret(interpret: Optional[bool]) -> bool:
    return jax.default_backend() == "cpu" if interpret is None else interpret


@functools.partial(
    jax.jit, static_argnames=("window", "chunk_attn", "block_q", "block_kv", "interpret")
)
def flash_attention(
    q: jax.Array,  # (B, Sq, Hq, Dh)
    k: jax.Array,  # (B, Skv, Hkv, Dh)
    v: jax.Array,  # (B, Skv, Hkv, Dh)
    q_positions: jax.Array,   # (B, Sq)
    kv_positions: jax.Array,  # (B, Skv)
    window: Optional[int] = None,
    chunk_attn: Optional[int] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Model-layout flash attention with VMEM-demoted accumulators."""
    b, sq, hq, dh = q.shape
    hkv = k.shape[2]
    groups = hq // hkv
    interp = _interpret(interpret)

    # flatten (B, H) and broadcast GQA groups
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, sq, dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), groups, axis=1).reshape(b * hq, -1, dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), groups, axis=1).reshape(b * hq, -1, dh)
    qp = jnp.repeat(q_positions[:, None, :], hq, axis=1).reshape(b * hq, sq)
    kp = jnp.repeat(kv_positions[:, None, :], hq, axis=1).reshape(b * hq, -1)

    out = fa.flash_attention_bh(
        qf, kf, vf, qp, kp,
        window=window, chunk=chunk_attn,
        block_q=block_q, block_kv=block_kv, interpret=interp,
    )
    return out.reshape(b, hq, sq, dh).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("chunk", "head_block", "interpret"))
def mamba2_ssd(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H)
    a: jax.Array,    # (H,)
    bm: jax.Array,   # (B, S, N)
    cm: jax.Array,   # (B, S, N)
    chunk: int = 256,
    head_block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    interp = _interpret(interpret)
    return ssd.ssd_pallas(
        x, dt, a, bm, cm, chunk=chunk, head_block=head_block, interpret=interp
    )
