"""Decode attention over a stacked KV cache, reading each slot's live blocks only.

One query token per slot against entry ``index`` of the stacked caches a
decode step holds (a layer's, or a shared block's application's).  The
caches are read as they are stored, positions minor: ``(N, B, Hkv, Dh, S)``,
the stored bytes of the model's ``(N, B, S, Hkv, Dh)`` caches.

Slot ``b`` attends to the positions ``[lo[b], hi[b])``.  The grid runs over
(slot, block of KV heads, block of positions), with each slot's bounds and
the entry's index prefetched as scalars.  The ``index_map`` clamps the block
of positions to the slot's live blocks: a step outside them repeats the
index of its neighbour, so no copy is issued for it, and its body is skipped
(``pl.when``).  The steps after a slot's last live block name the next
slot's first, whose copy then runs beside the last block's work.  The
partial blocks at both ends are masked inside.

The online softmax's running max ``m``, normaliser ``l`` and weighted sum
``acc`` stay in VMEM scratch across the blocks of positions, for each query
head (grouped over its KV head) and each of the 128 lanes of positions (a
cache shorter than that: each position), so that a step reduces over ``Dh``
(sublanes) and never across lanes.  After a slot's last block the lanes are
merged, and ``m``, ``l``, ``acc`` are returned unnormalised, for the caller
to merge with the token's own key and value.  Scores and sums are float32
multiply-and-reduce over the bfloat16 cache entries.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LANE, NEG_INF, VMEM_LIMIT_BYTES

#: bytes of K, and again of V, that one grid step reads: enough that a step's
#: copy outlasts the step's fixed cost, few enough positions that a slot's
#: last block wastes little
BLOCK_BYTES = 1 << 20


def block_sizes(hkv: int, dh: int, max_len: int, itemsize: int = 2) -> Tuple[int, int]:
    """(KV heads, positions) per block for caches of ``hkv`` heads of ``dh``
    and ``max_len`` positions: the fewest lanes of positions, a divisor of
    ``max_len``, that give :data:`BLOCK_BYTES` over all the heads; then the
    most heads, a divisor of ``hkv``, within twice that."""
    if max_len % LANE:
        return hkv, max_len
    per_lane_block = hkv * dh * itemsize * LANE
    block = min(LANE * -(-BLOCK_BYTES // per_lane_block), max_len)
    while max_len % block:
        block -= LANE
    heads = max([d for d in range(1, hkv + 1)
                 if hkv % d == 0 and d * dh * block * itemsize <= 2 * BLOCK_BYTES] or [1])
    return heads, block


def kv_index(i, h, s, lo, hi, *, slots: int, n_heads: int, block: int, n_blocks: int):
    """(slot, head block, block of positions) that grid step ``(i, h, s)``
    copies, for slots reading ``[lo, hi)`` (indexable by slot): ``s``
    clamped to the blocks slot ``i``'s range touches (block 0 where it is
    empty), so a step outside them repeats a live block's index and issues
    no copy; after the last of them, the first block of the grid's next
    (slot, head block), so that its copy overlaps this slot's last block."""
    def clamp(s, i):
        last = jnp.minimum(jnp.maximum(hi[i] - 1, 0) // block, n_blocks - 1)
        return jnp.minimum(jnp.maximum(s, jnp.minimum(lo[i] // block, last)), last)

    wrap = h + 1 == n_heads
    i_next = jnp.where(wrap, i + 1, i)
    ahead = jnp.logical_and(s > clamp(n_blocks - 1, i), i_next < slots)
    i_next = jnp.minimum(i_next, slots - 1)
    return (jnp.where(ahead, i_next, i), jnp.where(ahead, jnp.where(wrap, 0, h + 1), h),
            jnp.where(ahead, clamp(0, i_next), clamp(s, i)))


def read_share(positions: np.ndarray, max_len: int, block: int) -> Tuple[int, int]:
    """(blocks copied, blocks stored) of one cache entry for slots at
    ``positions`` that attend to every position before their own: the
    copies :func:`kv_index` makes over one head block, counted on the host."""
    hi = np.minimum(np.asarray(positions, np.int64), max_len)
    n_blocks = max_len // block
    read = np.maximum(np.minimum(-(-hi // block), n_blocks), 1)
    return int(read.sum()), n_blocks * len(hi)


def _kernel(index_ref, lo_ref, hi_ref, q_ref, k_ref, v_ref, o_ref, ml_ref,
            q_scr, m_scr, l_scr, acc_scr, *, block: int):
    """One grid step, all the block's heads at once; each lane of positions
    is an online softmax of its own until ``_finish`` merges the lanes."""
    del index_ref
    b, s = pl.program_id(0), pl.program_id(2)
    lo, hi = lo_ref[b], hi_ref[b]
    start = s * block
    groups, heads, dh, width = acc_scr.shape

    @pl.when(s == 0)
    def _init():
        for g in range(groups):
            for h in range(heads):
                j = h * groups + g
                q_scr[g, h] = jnp.broadcast_to(q_ref[:, j:j + 1], (dh, width))
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(jnp.logical_and(start < hi, start + block > lo))
    def _step():
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, width), 2)
        lanes = [pl.ds(c * width, width) for c in range(block // width)]
        live = [jnp.logical_and(start + c * width + lane >= lo, start + c * width + lane < hi)
                for c in range(len(lanes))]
        scores = [[] for _ in range(groups)]  # (heads, 1, width) per group and lane block
        for at, ok in zip(lanes, live):
            k = k_ref[:, :, at].astype(jnp.float32)
            for g in range(groups):
                scores[g].append(
                    jnp.where(ok, jnp.sum(k * q_scr[g], axis=1, keepdims=True), NEG_INF))
        p, acc = [], []
        for g in range(groups):
            m_prev = m_scr[g]
            m_new = functools.reduce(jnp.maximum, scores[g], m_prev)
            corr = jnp.exp(m_prev - m_new)
            p.append([jnp.where(ok, jnp.exp(sc - m_new), 0.0)
                      for sc, ok in zip(scores[g], live)])
            l_scr[g] = l_scr[g] * corr + functools.reduce(jnp.add, p[g])
            m_scr[g] = m_new
            acc.append(acc_scr[g] * corr)
        for c, at in enumerate(lanes):
            v = v_ref[:, :, at].astype(jnp.float32)
            for g in range(groups):
                acc[g] = acc[g] + v * p[g][c]
        for g in range(groups):
            acc_scr[g] = acc[g]

    @pl.when(s == pl.num_programs(2) - 1)
    def _finish():
        for g in range(groups):
            m = m_scr[g]
            m_all = jnp.max(m, axis=2, keepdims=True)
            f = jnp.exp(m - m_all)
            l_all = jnp.sum(l_scr[g] * f, axis=2, keepdims=True)
            acc_all = jnp.sum(acc_scr[g] * f, axis=2, keepdims=True)  # (heads, dh, 1)
            for h in range(heads):
                j = h * groups + g
                o_ref[:, j:j + 1] = acc_all[h]
                ml_ref[0:1, j:j + 1] = m_all[h]
                ml_ref[1:2, j:j + 1] = l_all[h]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def decode_attention(
    q: jax.Array,  # (B, Hq, Dh)
    k_all: jax.Array,  # (N, B, Hkv, Dh, S)
    v_all: jax.Array,  # (N, B, Hkv, Dh, S)
    index: jax.Array,  # () int: the entry to read
    lo: jax.Array,  # (B,) int32: first live position
    hi: jax.Array,  # (B,) int32: one past the last, at most S
    *,
    scale: float,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """-> (m (B, Hq), l (B, Hq), acc (B, Hq, Dh)), float32 and unnormalised:
    ``m`` the largest score (``scale`` times q·k) of each query head over its
    live positions (:data:`NEG_INF` where it has none), ``l`` the sum of
    ``exp(score - m)`` and ``acc`` that sum weighting the values.  Jitted,
    so that the calls of one decode step with the same shapes (a hybrid's
    applications) trace and lower the kernel once."""
    b, hq, dh = q.shape
    _, _, hkv, _, max_len = k_all.shape
    groups = hq // hkv
    heads, block = block_sizes(hkv, dh, max_len, k_all.dtype.itemsize)
    n_heads, n_blocks = hkv // heads, max_len // block
    rows = heads * groups
    width = LANE if block % LANE == 0 else block  # lanes of the partial sums

    # (B, head blocks, Dh, rows), scaled: a block's query heads are lanes, Dh sublanes
    q_t = (q.astype(jnp.float32) * scale).reshape(b, n_heads, rows, dh).swapaxes(2, 3)

    def kv_map(i, h, s, index, lo, hi):
        slot, head, at = kv_index(i, h, s, lo, hi, slots=b, n_heads=n_heads, block=block,
                                  n_blocks=n_blocks)
        return index[0], slot, head, 0, at

    def per_slot(i, h, s, *_):
        return i, h, 0, 0

    kv_spec = pl.BlockSpec((None, None, heads, dh, block), kv_map)
    o_t, ml = pl.pallas_call(
        functools.partial(_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n_heads, n_blocks),
            in_specs=[pl.BlockSpec((None, None, dh, rows), per_slot), kv_spec, kv_spec],
            out_specs=[pl.BlockSpec((None, None, dh, rows), per_slot),
                       pl.BlockSpec((None, None, 2, rows), per_slot)],
            scratch_shapes=[
                pltpu.VMEM((groups, heads, dh, width), jnp.float32),
                pltpu.VMEM((groups, heads, 1, width), jnp.float32),
                pltpu.VMEM((groups, heads, 1, width), jnp.float32),
                pltpu.VMEM((groups, heads, dh, width), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((b, n_heads, dh, rows), jnp.float32),
                   jax.ShapeDtypeStruct((b, n_heads, 2, rows), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="decode_attention",
    )(jnp.reshape(index, (1,)).astype(jnp.int32), lo.astype(jnp.int32), hi.astype(jnp.int32),
      q_t, k_all, v_all)
    acc = o_t.swapaxes(2, 3).reshape(b, hq, dh)
    return ml[:, :, 0].reshape(b, hq), ml[:, :, 1].reshape(b, hq), acc
