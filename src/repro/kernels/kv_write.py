"""Each slot's new K and V rows written into the stacked KV caches, in place.

A decode step writes one row per slot into every entry (a layer's, or a
shared block's application's) of the stacked caches, at the slot's
position.  The caches are stored positions minor, so the kernel sees them
as ``(N, B, Hkv, Dh, S)``, the stored bytes of the model's ``(N, B, S, Hkv,
Dh)``, as :mod:`repro.kernels.decode_attention` does.  A row at position
``p`` is then one lane of every tile that holds it; the least a write can
store is those tiles.

The grid runs over (slot, entry, block of KV heads), with the slots'
positions prefetched as scalars.  Each step copies in the aligned window of
128 positions that holds the slot's position (the whole cache where it is
not a multiple of 128 positions long), sets the position's lane from the
slot's new rows, and the pipeline writes the window back.  Both caches are
aliased to the outputs, so a donated cache is updated where it is stored.
A position is clamped to ``[0, S - 1]``, as ``dynamic_update_slice``
clamps its offset; only bytes are copied.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import LANE, SUBLANE, VMEM_LIMIT_BYTES, _align_up

#: bytes of K, and again of V, that one grid step rewrites: few enough that
#: the window's buffers, in and out and double-buffered, fit the kernel's VMEM
BLOCK_BYTES = 1 << 20


def _padded(dh: int, itemsize: int) -> int:
    """``dh`` rounded up to whole tiles of a dtype of ``itemsize`` bytes,
    whose rows are 8 of 32 bits, packed."""
    return _align_up(dh, SUBLANE * max(1, 4 // itemsize))


def block_sizes(hkv: int, dh: int, max_len: int, itemsize: int = 2) -> Tuple[int, int]:
    """(KV heads, positions) per block for caches of ``hkv`` heads of ``dh``
    and ``max_len`` positions: one aligned window of 128 positions (the
    whole cache where ``max_len`` is not a multiple of 128), and the most
    heads, a divisor of ``hkv``, within :data:`BLOCK_BYTES` (one at least)."""
    width = LANE if max_len % LANE == 0 else max_len
    per_head = _padded(dh, itemsize) * width * itemsize
    heads = max([d for d in range(1, hkv + 1)
                 if hkv % d == 0 and d * per_head <= BLOCK_BYTES] or [1])
    return heads, width


def write_bytes(shape: Tuple[int, ...], itemsize: int) -> int:
    """Bytes of the tiles one call rewrites in one of caches of ``shape``
    ``(N, B, S, Hkv, Dh)``, read and written alike: each slot's window of
    positions in every entry and head, ``Dh`` padded to whole tiles."""
    n, b, max_len, hkv, dh = shape
    _, width = block_sizes(hkv, dh, max_len, itemsize)
    return n * b * hkv * _padded(dh, itemsize) * width * itemsize


def _kernel(pos_ref, k_row_ref, v_row_ref, k_ref, v_ref, k_out, v_out, *, max_len: int):
    """One window of every head of the block: the slot's lane set from its
    rows, the others copied."""
    p = jnp.clip(pos_ref[pl.program_id(0)], 0, max_len - 1)
    heads, dh, width = k_ref.shape
    hit = jax.lax.broadcasted_iota(jnp.int32, (dh, width), 1) == p % width
    for rows, cache, out in ((k_row_ref, k_ref, k_out), (v_row_ref, v_ref, v_out)):
        new = rows[...]  # (Dh, heads): a head's row is a lane
        for h in range(heads):
            out[h] = jnp.where(hit, new[:, h:h + 1], cache[h])


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_rows(
    k_all: jax.Array,  # (N, B, Hkv, Dh, S)
    v_all: jax.Array,  # (N, B, Hkv, Dh, S)
    k_rows: jax.Array,  # (N, B, Hkv, Dh)
    v_rows: jax.Array,  # (N, B, Hkv, Dh)
    positions: jax.Array,  # (B,) int
    *,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """-> (k_all, v_all) with slot ``b``'s rows of every entry at position
    ``positions[b]``, clamped to ``[0, S - 1]``.  The caches are aliased to
    the outputs: in place where the caller's are donated.  Jitted, as
    :func:`repro.kernels.decode_attention.decode_attention` is: a program
    holds the kernel in a function of its own."""
    n, b, hkv, dh, max_len = k_all.shape
    heads, width = block_sizes(hkv, dh, max_len, k_all.dtype.itemsize)

    def window(i, e, h, pos):
        return e, i, h, 0, jnp.clip(pos[i], 0, max_len - 1) // width

    def row(i, e, h, pos):
        return e, i, h, 0, 0

    def by_block(rows):
        """(N, B, head blocks, Dh, heads): a block's rows, ``Dh`` on sublanes
        as in the cache, its heads on lanes."""
        rows = rows.astype(k_all.dtype).reshape(n, b, hkv // heads, heads, dh)
        return rows.swapaxes(3, 4)

    cache = pl.BlockSpec((None, None, heads, dh, width), window)
    new = pl.BlockSpec((None, None, None, dh, heads), row)
    return pl.pallas_call(
        functools.partial(_kernel, max_len=max_len),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n, hkv // heads),
            in_specs=[new, new, cache, cache],
            out_specs=[cache, cache],
        ),
        out_shape=[jax.ShapeDtypeStruct(k_all.shape, k_all.dtype),
                   jax.ShapeDtypeStruct(v_all.shape, v_all.dtype)],
        input_output_aliases={3: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name="kv_write",
    )(positions.astype(jnp.int32), by_block(k_rows), by_block(v_rows),
      k_all, v_all)
