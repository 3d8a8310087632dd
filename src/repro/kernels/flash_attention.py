"""Flash attention with VMEM-demoted accumulators (the RegDem TPU kernel).

Hardware adaptation of the paper's register demotion (DESIGN.md §2):

* GPU RegDem keeps spilled registers in *shared memory* so occupancy stays
  high.  On TPU the scarce fast tier is VREGs + the per-block working set;
  the software-managed on-chip tier is **VMEM**.  This kernel keeps the
  online-softmax running state — the (bq,) running max ``m``, the (bq,)
  normalizer ``l`` and the (bq, dh) output accumulator — in explicit **VMEM
  scratch** across the KV-block grid dimension, instead of writing per-block
  partial products to HBM and re-normalizing in a second pass (the
  "local-memory spill" analogue a naive lowering produces).
* Block shapes are the register-count analogue: larger (bq, bkv) blocks =
  fewer grid steps (better "single-thread" efficiency) but a larger VMEM
  footprint (lower "occupancy").  :func:`choose_block_sizes` plays the role
  of the paper's occupancy-cliff target chooser: it picks the largest
  MXU-aligned blocks whose working set fits the VMEM budget.

Grid: (batch x heads, q_blocks, kv_blocks) with kv innermost so the scratch
accumulators carry across kv steps; masking supports causal, sliding-window
(gemma3) and chunked (llama4) patterns via position arrays.

Validated against :mod:`repro.kernels.ref` in interpret mode (CPU) across
shape/dtype sweeps; ``tests/test_chip_compile.py`` compiles it for a
described TPU v5e at stablelm_3b widths.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: scoped VMEM (bytes) the kernels ask the compiler for, and the budget
#: both size their blocks against.  Unasked, a TPU v5e kernel gets 16 MiB,
#: which 2048x2048 attention blocks overflow.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
#: MXU tile alignment
LANE = 128
SUBLANE = 8


def _align_up(x: int, unit: int) -> int:
    return -(-x // unit) * unit


def choose_block_sizes(
    seq_q: int, seq_kv: int, head_dim: int, dtype_bytes: int = 2,
    vmem_budget: int = VMEM_LIMIT_BYTES,
) -> Tuple[int, int]:
    """Pick (bq, bkv): largest MXU-aligned blocks fitting the VMEM budget.

    Working set per grid step:
      operand blocks q (bq, dh), k/v (bkv, dh) and out (bq, dh), each
      double-buffered for the pipelined HBM<->VMEM copies;
      f32 values of the body: the q/k/v upcasts, scores and probabilities
      (bq, bkv) each, and the acc (bq, dh) + m/l (bq) scratch.

    Both returned block sizes are always SUBLANE-aligned and never exceed
    the SUBLANE-rounded sequence length; sequences that are not a multiple
    of the chosen block are padded by :func:`flash_attention_bh` (masked
    via the position arrays), so any (bq, bkv) this returns is launchable.
    """
    def fits(bq: int, bkv: int) -> bool:
        blocks = 2 * (2 * bq + 2 * bkv) * head_dim * dtype_bytes
        f32 = ((2 * bq + 2 * bkv) * head_dim + 2 * bq * bkv + 2 * bq) * 4
        return blocks + f32 <= vmem_budget

    # a short sequence gets one SUBLANE-aligned block covering it entirely;
    # longer ones pick from the MXU-friendly ladder (padding covers the
    # partial final block)
    sq = _align_up(max(seq_q, 1), SUBLANE)
    skv = _align_up(max(seq_kv, 1), SUBLANE)
    ladder = [2048, 1024, 512, 256, 128]
    cand_q = [c for c in ladder if c <= sq] or [sq]
    cand_kv = [c for c in ladder if c <= skv] or [skv]
    for bq in cand_q:
        for bkv in cand_kv:
            if fits(bq, bkv):
                return bq, bkv
    return cand_q[-1], cand_kv[-1]


def _attention_kernel(
    # refs (blocked by BlockSpec)
    q_ref,      # (1, bq, dh)
    k_ref,      # (1, bkv, dh)
    v_ref,      # (1, bkv, dh)
    qpos_ref,   # (1, 1, bq)
    kpos_ref,   # (1, 1, bkv)
    o_ref,      # (1, bq, dh)
    # VMEM scratch: the demoted accumulators
    m_scr,      # (bq,)
    l_scr,      # (bq,)
    acc_scr,    # (bq, dh)
    *,
    kv_blocks: int,
    scale: float,
    window: Optional[int],
    chunk: Optional[int],
):
    kv_idx = pl.program_id(2)

    # ---- init demoted accumulators at the first kv block -------------------
    @pl.when(kv_idx == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)          # (bq, dh)
    k = k_ref[0].astype(jnp.float32)          # (bkv, dh)
    v = v_ref[0].astype(jnp.float32)
    qp = qpos_ref[0, 0]                         # (bq,)
    kp = kpos_ref[0, 0]                         # (bkv,)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                   # (bq, bkv)

    ok = jnp.logical_and(kp[None, :] >= 0, kp[None, :] <= qp[:, None])
    if window is not None:
        ok = jnp.logical_and(ok, kp[None, :] > qp[:, None] - window)
    if chunk is not None:
        ok = jnp.logical_and(ok, (kp[None, :] // chunk) == (qp[:, None] // chunk))
    s = jnp.where(ok, s, NEG_INF)

    # ---- online softmax over the demoted state ------------------------------
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_scr[...] * corr + jnp.sum(p, axis=1)
    pv = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_new = acc_scr[...] * corr[:, None] + pv
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    # ---- final normalization at the last kv block ---------------------------
    @pl.when(kv_idx == kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bh(
    q: jax.Array,        # (BH, Sq, Dh) — batch*heads flattened
    k: jax.Array,        # (BH, Skv, Dh)
    v: jax.Array,        # (BH, Skv, Dh)
    q_positions: jax.Array,   # (BH, Sq) int32
    kv_positions: jax.Array,  # (BH, Skv) int32
    *,
    window: Optional[int] = None,
    chunk: Optional[int] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Core pallas_call on (batch*heads)-flattened operands.

    Sequence lengths need not be multiples of the block sizes (nor of
    SUBLANE): operands are zero-padded up to the next block boundary and
    the padded positions are masked out through the position arrays —
    padded kv rows get position -1 (always masked: ``kp >= 0`` fails) and
    padded q rows produce finite garbage that is sliced off before return.
    """
    bh, sq, dh = q.shape
    skv = k.shape[1]
    scale = 1.0 / math.sqrt(dh)
    auto = choose_block_sizes(sq, skv, dh)
    bq = _align_up(min(block_q or auto[0], _align_up(sq, SUBLANE)), SUBLANE)
    bkv = _align_up(min(block_kv or auto[1], _align_up(skv, SUBLANE)), SUBLANE)
    pad_q = _align_up(sq, bq) - sq
    pad_kv = _align_up(skv, bkv) - skv
    if pad_q or pad_kv:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0)))
        # padded kv columns carry position -1: masked out everywhere.
        # padded q rows also carry -1 — their outputs are dropped below.
        q_positions = jnp.pad(q_positions, ((0, 0), (0, pad_q)), constant_values=-1)
        kv_positions = jnp.pad(kv_positions, ((0, 0), (0, pad_kv)), constant_values=-1)
    sq_p, skv_p = sq + pad_q, skv + pad_kv
    q_blocks, kv_blocks = sq_p // bq, skv_p // bkv

    kernel = functools.partial(
        _attention_kernel,
        kv_blocks=kv_blocks,
        scale=scale,
        window=window,
        chunk=chunk,
    )
    grid = (bh, q_blocks, kv_blocks)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bkv), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(q, k, v, q_positions[:, None, :], kv_positions[:, None, :])
    return out[:, :sq] if pad_q else out

