"""Mamba2 SSD chunk-scan Pallas kernel with VMEM-resident recurrent state.

The RegDem adaptation for the SSM family: the inter-chunk recurrent state
``h (heads_blk, P, N)`` is the demoted register — it lives in **VMEM
scratch** across the chunk-grid dimension instead of being written back to
HBM between chunks (which is what the pure-JAX ``lax.scan`` formulation
materializes as carry traffic).

Grid: (batch, head_blocks, chunks) with chunks innermost.  Per step the
kernel loops over the heads of its block and, per head, computes the
intra-chunk quadratic dual form and folds the carried state, all in fp32:

    L        = exp(segsum(dt*a))          (Q, Q) lower-triangular decay
    y_intra  = (C B^T . L . dt) x
    y_inter  = C h_prev . decay_from_start
    h       <- h * exp(sum dt*a) + B^T (dt * decay_to_end * x)

Operands are laid out heads-first, ``(B, H, S, P)`` and ``(B, H, 1, S)``,
so every per-head value is a 2-D (Q, *) tile: the (Q, Q) intermediates
exist for one head at a time, and per-position scalars are (1, Q) rows.  A
row becomes a column, and the chunk-local cumulative sum is taken, by
masked lane reductions over a (Q, Q) tile (the TPU lowering has no
``cumsum``); both are exact f32 sums.

Validated against :func:`repro.kernels.ref.ssd_reference` in interpret mode;
``tests/test_chip_compile.py`` compiles it for a described TPU v5e at
mamba2_370m widths.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import VMEM_LIMIT_BYTES

_HIGHEST = jax.lax.Precision.HIGHEST


def _dot(a, b, contract):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=_HIGHEST, preferred_element_type=jnp.float32,
    )


def _ssd_kernel(
    x_ref,    # (1, hb, Q, P)
    dt_ref,   # (1, hb, 1, Q)
    da_ref,   # (1, hb, 1, Q)  dt * a
    b_ref,    # (1, Q, N)
    c_ref,    # (1, Q, N)
    y_ref,    # (1, hb, Q, P)
    hlast_ref,  # (1, hb, P, N)
    h_scr,    # VMEM (hb, P, N) — the demoted recurrent state
    *,
    n_chunks: int,
):
    ci = pl.program_id(2)
    hb = h_scr.shape[0]

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    b = b_ref[0].astype(jnp.float32)       # (Q, N)
    c = c_ref[0].astype(jnp.float32)       # (Q, N)
    q = b.shape[0]
    scores = _dot(c, b, ((1,), (1,)))      # (Q, Q), shared by every head
    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower = row >= col
    diag = row == col

    def head(i, carry):
        x = x_ref[0, i].astype(jnp.float32)    # (Q, P)
        dt = dt_ref[0, i].astype(jnp.float32)  # (1, Q)
        da = da_ref[0, i]                      # (1, Q)

        # chunk-local cumulative sum of da, as a column and as a row
        cum_c = jnp.sum(jnp.where(lower, da, 0.0), axis=1, keepdims=True)     # (Q, 1)
        cum_r = jnp.sum(jnp.where(diag, cum_c, 0.0), axis=0, keepdims=True)   # (1, Q)
        total = cum_r[:, q - 1:]                                                # (1, 1)
        dt_c = jnp.sum(jnp.where(diag, dt, 0.0), axis=1, keepdims=True)       # (Q, 1)

        # ---- intra-chunk quadratic dual form --------------------------------
        decay = jnp.where(lower, jnp.exp(cum_c - cum_r), 0.0)  # (Q, Q)
        w = scores * decay * dt
        y_intra = _dot(w, x, ((1,), (0,)))                     # (Q, P)

        # ---- inter-chunk from the VMEM-resident state ------------------------
        h_prev = h_scr[i]                                      # (P, N)
        y_inter = _dot(c, h_prev, ((1,), (1,))) * jnp.exp(cum_c)
        y_ref[0, i] = (y_intra + y_inter).astype(y_ref.dtype)

        # ---- state update ------------------------------------------------------
        weight = dt_c * jnp.exp(total - cum_c)                 # (Q, 1)
        h_scr[i] = h_prev * jnp.exp(total) + _dot(x * weight, b, ((0,), (0,)))
        return carry

    jax.lax.fori_loop(0, hb, head, 0)

    @pl.when(ci == n_chunks - 1)
    def _emit():
        hlast_ref[0] = h_scr[...].astype(hlast_ref.dtype)


def ssd_vmem_bytes(hb: int, chunk: int, P: int, N: int, itemsize: int) -> int:
    """VMEM one grid step holds: double-buffered x/y/dt/da/B/C/h_last
    blocks (a (1, Q) row pads to 8 sublanes), the f32 state scratch, and
    one head's f32 (Q, Q) and (Q, P) temporaries."""
    blocks = 2 * (
        2 * hb * chunk * P * itemsize
        + 2 * hb * 8 * chunk * 4
        + 2 * chunk * N * itemsize
        + hb * P * N * 4
    )
    temps = 8 * chunk * chunk * 4 + 4 * chunk * P * 4
    return blocks + hb * P * N * 4 + temps


def ssd_pallas(
    x: jax.Array,    # (B, S, H, P)
    dt: jax.Array,   # (B, S, H) post-softplus
    a: jax.Array,    # (H,) negative
    bm: jax.Array,   # (B, S, N)
    cm: jax.Array,   # (B, S, N)
    *,
    chunk: int = 256,
    head_block: Optional[int] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), h_last (B,H,P,N)).

    ``head_block`` defaults to the largest divisor of H whose step fits
    :data:`~repro.kernels.flash_attention.VMEM_LIMIT_BYTES`.
    """
    B, S, H, P = x.shape
    N = bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    hb = head_block or max(
        d for d in range(1, H + 1)
        if H % d == 0
        and ssd_vmem_bytes(d, chunk, P, N, x.dtype.itemsize) <= VMEM_LIMIT_BYTES
    )
    assert H % hb == 0, (H, hb)

    xh = x.transpose(0, 2, 1, 3)                                  # (B, H, S, P)
    dth = dt.transpose(0, 2, 1)[:, :, None, :]                    # (B, H, 1, S)
    dah = dth.astype(jnp.float32) * a.astype(jnp.float32)[None, :, None, None]

    kernel = functools.partial(_ssd_kernel, n_chunks=nc)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(B, H // hb, nc),
        in_specs=[
            pl.BlockSpec((1, hb, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, hb, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, hb, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, hb, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(xh, dth, dah, bm, cm)
    return y.transpose(0, 2, 1, 3), h_last
