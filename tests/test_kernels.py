"""Per-kernel correctness: interpret-mode Pallas vs pure-jnp oracles.

Sweeps shapes and dtypes per the deliverable contract; every cell asserts
allclose against :mod:`repro.kernels.ref`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import choose_block_sizes


def _mk_attention(B, Sq, Skv, Hq, Hkv, Dh, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, Dh), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, Dh), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, Dh), jnp.float32).astype(dtype)
    qpos = jnp.broadcast_to(
        jnp.arange(Skv - Sq, Skv, dtype=jnp.int32)[None], (B, Sq)
    )
    kpos = jnp.broadcast_to(jnp.arange(Skv, dtype=jnp.int32)[None], (B, Skv))
    return q, k, v, qpos, kpos


def _ref_model_layout(q, k, v, qpos, kpos, **kw):
    B, Sq, Hq, Dh = q.shape
    Hkv = k.shape[2]
    g = Hq // Hkv
    qf = q.transpose(0, 2, 1, 3).reshape(B * Hq, Sq, Dh)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, 1).reshape(B * Hq, -1, Dh)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, 1).reshape(B * Hq, -1, Dh)
    qp = jnp.repeat(qpos[:, None, :], Hq, 1).reshape(B * Hq, Sq)
    kp = jnp.repeat(kpos[:, None, :], Hq, 1).reshape(B * Hq, -1)
    r = ref.attention_reference(qf, kf, vf, qp, kp, **kw)
    return r.reshape(B, Hq, Sq, Dh).transpose(0, 2, 1, 3)


ATTN_SHAPES = [
    # (B, Sq, Skv, Hq, Hkv, Dh)
    (1, 128, 128, 2, 2, 64),     # MHA square
    (2, 128, 128, 4, 1, 64),     # extreme GQA (gemma3-style kv=1)
    (2, 64, 256, 4, 2, 128),     # decode-ish: short q, long kv
    (1, 256, 256, 8, 4, 128),    # GQA 2:1
    (2, 128, 128, 4, 4, 256),    # wide heads (gemma3 head_dim)
]


@pytest.mark.parametrize("shape", ATTN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(shape, dtype):
    B, Sq, Skv, Hq, Hkv, Dh = shape
    q, k, v, qpos, kpos = _mk_attention(B, Sq, Skv, Hq, Hkv, Dh, dtype)
    out = ops.flash_attention(q, k, v, qpos, kpos, block_q=64, block_kv=64)
    want = _ref_model_layout(q, k, v, qpos, kpos)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        out.astype(jnp.float32), want.astype(jnp.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("window", [16, 64])
def test_flash_attention_sliding_window(window):
    q, k, v, qpos, kpos = _mk_attention(2, 128, 128, 4, 2, 64, jnp.float32)
    out = ops.flash_attention(q, k, v, qpos, kpos, window=window, block_q=64, block_kv=64)
    want = _ref_model_layout(q, k, v, qpos, kpos, window=window)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("chunk", [32, 64])
def test_flash_attention_chunked_mask(chunk):
    q, k, v, qpos, kpos = _mk_attention(2, 128, 128, 4, 2, 64, jnp.float32)
    out = ops.flash_attention(q, k, v, qpos, kpos, chunk_attn=chunk, block_q=64, block_kv=64)
    want = _ref_model_layout(q, k, v, qpos, kpos, chunk=chunk)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bq,bkv", [(32, 32), (64, 128), (128, 64)])
def test_flash_attention_block_shapes(bq, bkv):
    """Block shape must not change the math (the demotion-knob invariant)."""
    q, k, v, qpos, kpos = _mk_attention(1, 128, 128, 2, 2, 64, jnp.float32)
    base = ops.flash_attention(q, k, v, qpos, kpos, block_q=128, block_kv=128)
    out = ops.flash_attention(q, k, v, qpos, kpos, block_q=bq, block_kv=bkv)
    np.testing.assert_allclose(out, base, atol=2e-5, rtol=2e-5)


def test_choose_block_sizes_alignment_and_budget():
    bq, bkv = choose_block_sizes(4096, 4096, 128)
    assert bq % 128 == 0 and bkv % 128 == 0
    # working set must respect the budget it was given
    small_bq, small_bkv = choose_block_sizes(4096, 4096, 128, vmem_budget=2 * 2**20)
    assert small_bq * small_bkv <= bq * bkv
    # short sequences never exceed their length
    bq, bkv = choose_block_sizes(64, 64, 64)
    assert bq <= 64 and bkv <= 64


def test_choose_block_sizes_always_sublane_aligned():
    """Regression: the old `bq > max(seq_q, LANE)` guard admitted bq=128 for
    seq_q < 128 and then returned the raw (possibly unaligned) seq_q.  Every
    returned block must now be SUBLANE-aligned regardless of sequence
    length, and launchable via padding (no divisibility requirement)."""
    from repro.kernels.flash_attention import SUBLANE

    for sq in (1, 7, 17, 100, 120, 127, 129, 200, 333, 4096):
        for skv in (1, 40, 200, 1500, 32768):
            bq, bkv = choose_block_sizes(sq, skv, 128)
            assert bq % SUBLANE == 0 and bkv % SUBLANE == 0, (sq, skv, bq, bkv)
            assert bq <= max(sq + SUBLANE - 1, SUBLANE), (sq, bq)


ODD_SHAPES = [
    # (B, Sq, Skv, Hq, Hkv, Dh, window, chunk) — none block-aligned
    (1, 200, 200, 2, 2, 64, None, None),     # partial final blocks both axes
    (2, 17, 40, 4, 2, 64, None, None),       # tiny unaligned lengths
    (1, 1, 333, 4, 4, 64, None, None),       # decode-style single q row
    (2, 100, 100, 4, 2, 64, 32, None),       # sliding window over padding
    (1, 200, 200, 2, 2, 64, None, 64),       # chunked mask over padding
    (1, 129, 257, 2, 1, 128, None, None),    # just past a block boundary
]


@pytest.mark.parametrize("shape", ODD_SHAPES)
def test_flash_attention_unaligned_lengths(shape):
    """Regression sweep for the partial-final-block path: odd/short lengths
    must produce exactly the reference result (padded rows/columns masked
    through the position arrays, never through luck)."""
    B, Sq, Skv, Hq, Hkv, Dh, window, chunk = shape
    q, k, v, qpos, kpos = _mk_attention(B, Sq, Skv, Hq, Hkv, Dh, jnp.float32)
    out = ops.flash_attention(q, k, v, qpos, kpos, window=window, chunk_attn=chunk)
    want = _ref_model_layout(q, k, v, qpos, kpos, window=window, chunk=chunk)
    assert out.shape == want.shape
    np.testing.assert_allclose(out, want, atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# Mamba2 SSD kernel
# ---------------------------------------------------------------------------

SSD_SHAPES = [
    # (B, S, H, P, N, chunk)
    (1, 64, 2, 16, 16, 16),
    (2, 128, 4, 32, 32, 32),
    (1, 96, 8, 16, 64, 32),     # mamba2-style wide state
    (2, 64, 4, 64, 16, 16),     # zamba2-style wide heads
]


def _mk_ssd(B, S, H, P, N, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = (jax.random.normal(ks[0], (B, S, H, P)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    bm = (jax.random.normal(ks[3], (B, S, N)) * 0.4).astype(dtype)
    cm = (jax.random.normal(ks[4], (B, S, N)) * 0.4).astype(dtype)
    return x, dt, a, bm, cm


@pytest.mark.parametrize("shape", SSD_SHAPES)
def test_ssd_kernel_shapes(shape):
    B, S, H, P, N, chunk = shape
    x, dt, a, bm, cm = _mk_ssd(B, S, H, P, N)
    y, h = ops.mamba2_ssd(x, dt, a, bm, cm, chunk=chunk)
    yr, hr = ref.ssd_reference(x, dt, a, bm, cm)
    np.testing.assert_allclose(y, yr, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h, hr, atol=2e-4, rtol=2e-4)


def test_ssd_kernel_bf16():
    x, dt, a, bm, cm = _mk_ssd(1, 64, 4, 16, 32, dtype=jnp.bfloat16)
    y, h = ops.mamba2_ssd(x, dt, a, bm, cm, chunk=16)
    yr, hr = ref.ssd_reference(x, dt, a, bm, cm)
    np.testing.assert_allclose(
        y.astype(jnp.float32), yr.astype(jnp.float32), atol=5e-2, rtol=5e-2
    )


def test_ssd_head_blocking_invariant():
    """Head-block size must not change results (VMEM footprint knob)."""
    x, dt, a, bm, cm = _mk_ssd(1, 64, 8, 16, 16)
    base, hb = ops.mamba2_ssd(x, dt, a, bm, cm, chunk=16, head_block=8)
    for blk in (1, 2, 4):
        y, h = ops.mamba2_ssd(x, dt, a, bm, cm, chunk=16, head_block=blk)
        np.testing.assert_allclose(y, base, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(h, hb, atol=1e-5, rtol=1e-5)


def test_ssd_matches_model_scan_path():
    """The kernel agrees with the model's lax.scan SSD (chunked dual form)."""
    from repro.models.mamba2 import ssd_chunked

    x, dt, a, bm, cm = _mk_ssd(2, 64, 4, 16, 32)
    y_kernel, h_kernel = ops.mamba2_ssd(x, dt, a, bm, cm, chunk=16)
    y_model, h_model = ssd_chunked(x, dt, a, bm[:, :, None, :], cm[:, :, None, :], chunk=16)
    np.testing.assert_allclose(y_kernel, y_model, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(h_kernel, h_model, atol=1e-4, rtol=1e-4)


# -- decode attention over a stacked cache ------------------------------------

DECODE_CASES = [
    # (Hq, Hkv, Dh, max_len, scale, window, chunk)
    pytest.param(32, 32, 80, 512, None, None, None, id="mha_32x80"),
    pytest.param(32, 32, 224, 512, 112 ** -0.5, None, None, id="mha_32x224_zamba2_scale"),
    pytest.param(28, 4, 128, 2048, None, None, None, id="gqa_28_4x128"),
    pytest.param(32, 32, 80, 512, None, 300, None, id="window_300"),
    pytest.param(32, 32, 80, 512, None, None, 192, id="chunk_192"),
]


@pytest.mark.parametrize("hq,hkv,dh,max_len,scale,window,chunk", DECODE_CASES)
def test_decode_attention_matches_xla(hq, hkv, dh, max_len, scale, window, chunk):
    """The kernel path of the decode attention (interpret mode), its own
    key and value merged outside, against the XLA ``attention_decode`` on
    the same entry of a stack of three caches: slots at position 1, either
    side of a block boundary, on it, and at the last position, in one
    batch."""
    from repro.kernels import decode_attention as da
    from repro.models.attention import attention_decode, attention_decode_kernel

    block = da.block_sizes(hkv, dh, max_len)[1]
    positions = jnp.array([1, block - 1, block, block + 1, max_len - 1], jnp.int32)
    b, n, index = positions.shape[0], 3, 1
    ks = jax.random.split(jax.random.PRNGKey(hq + dh), 5)
    q = jax.random.normal(ks[0], (b, 1, hq, dh), jnp.float32)
    k = jax.random.normal(ks[1], (b, 1, hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (b, 1, hkv, dh), jnp.float32)
    k_all = jax.random.normal(ks[3], (n, b, max_len, hkv, dh), jnp.float32).astype(jnp.bfloat16)
    v_all = jax.random.normal(ks[4], (n, b, max_len, hkv, dh), jnp.float32).astype(jnp.bfloat16)
    masks = dict(window=window, chunk_attn=chunk, scale=scale)

    got = attention_decode_kernel(q, k, v, k_all, v_all, jnp.int32(index), positions,
                                  interpret=True, **masks)
    want = attention_decode(q, k, v, k_all[index], v_all[index], positions, **masks)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("hkv,dh,max_len", [(32, 80, 2048), (32, 224, 2048), (4, 128, 2048),
                                            (4, 16, 32), (64, 256, 1024)])
def test_kv_read_share_counts_the_blocks_the_index_map_visits(hkv, dh, max_len):
    """The host's count of a decode step's cache blocks equals the copies the
    kernel's grid issues, one wherever a step's block index differs from the
    step before it, in every head block; and every live block is copied."""
    from repro.kernels import decode_attention as da

    heads, block = da.block_sizes(hkv, dh, max_len)
    n_heads, n_blocks = hkv // heads, max_len // block
    positions = np.array([0, 1, block - 1, block, block + 1, max_len - 1, max_len, max_len + 7])
    hi = np.minimum(positions, max_len)
    lo = np.zeros_like(hi)
    where = dict(slots=len(hi), n_heads=n_heads, block=block, n_blocks=n_blocks)
    steps = [tuple(int(x) for x in da.kv_index(i, h, s, lo, hi, **where))
             for i in range(len(hi)) for h in range(n_heads) for s in range(n_blocks)]
    copies = sum(1 for prev, cur in zip([None] + steps, steps) if cur != prev)
    read, stored = da.read_share(positions, max_len, block)
    assert (n_heads * read, stored) == (copies, n_blocks * len(positions))
    live = {(i, h, s) for i in range(len(hi)) for h in range(n_heads)
            for s in range(n_blocks) if s * block < hi[i]}
    assert live <= set(steps)


def test_decode_blocks_fit_the_cells_caches():
    """Blocks of whole lanes of positions that divide the cache, about
    :data:`BLOCK_BYTES` of K a step: stablelm_3b's 32 heads of 80 take 256
    positions, zamba2_7b's 32 heads of 224 take 128."""
    from repro.kernels import decode_attention as da

    assert da.block_sizes(32, 80, 2048) == (32, 256)
    assert da.block_sizes(32, 224, 2048) == (32, 128)
    assert da.block_sizes(4, 128, 2048) == (4, 1024)
    assert da.block_sizes(4, 16, 32) == (4, 32)
    assert da.block_sizes(4, 128, 640) == (4, 640)
