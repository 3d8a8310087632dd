"""Mamba2 (state-space duality / SSD) mixer — arXiv:2405.21060.

Chunked SSD algorithm in pure JAX: within chunks of length ``Q`` the
recurrence is computed in its quadratic "attention-like" dual form; across
chunks a ``jax.lax.scan`` carries the (H, P, N) recurrent state.

Register-demotion connection (DESIGN.md §2): the carried chunk state is the
demoted-register analogue — it stays resident (registers/VMEM) across the
chunk loop instead of being re-materialized from HBM, and the Pallas kernel
(:mod:`repro.kernels.mamba2_ssd`) makes that residency explicit with VMEM
scratch.

Decode is the O(1) recurrent update: ``h = dA * h + dt*B (x); y = C . h``.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import scopes
from .common import scan as common_scan, rms_norm, trunc_normal

Pytree = Any

D_CONV = 4  # depthwise causal conv width (mamba2 default)
N_GROUPS = 1


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def mamba_dims(d_model: int, ssm_heads: int, ssm_head_dim: int, d_state: int):
    d_inner = ssm_heads * ssm_head_dim
    conv_dim = d_inner + 2 * N_GROUPS * d_state
    return d_inner, conv_dim


def init_mamba_layer(
    key: jax.Array,
    d_model: int,
    ssm_heads: int,
    ssm_head_dim: int,
    d_state: int,
    dtype=jnp.bfloat16,
) -> Tuple[Dict[str, jax.Array], Dict[str, Tuple[str, ...]]]:
    H, P, N = ssm_heads, ssm_head_dim, d_state
    d_inner, conv_dim = mamba_dims(d_model, H, P, N)
    ks = jax.random.split(key, 4)
    proj_out = 2 * d_inner + 2 * N_GROUPS * N + H  # z, x, B, C, dt
    params = {
        "in_proj": trunc_normal(ks[0], (d_model, proj_out), std=1.0 / math.sqrt(d_model), dtype=dtype),
        "conv_w": trunc_normal(ks[1], (D_CONV, conv_dim), std=0.2, dtype=dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "a_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "d_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": jnp.zeros((d_inner,), dtype),
        "out_proj": trunc_normal(ks[2], (d_inner, d_model), std=1.0 / math.sqrt(d_inner), dtype=dtype),
        "ln": jnp.zeros((d_model,), dtype),
    }
    axes = {
        "in_proj": ("embed", "ff"),
        "conv_w": (None, "ff"),
        "conv_b": ("ff",),
        "a_log": (None,),
        "d_skip": (None,),
        "dt_bias": (None,),
        "norm": ("ff",),
        "out_proj": ("ff", "embed"),
        "ln": ("embed",),
    }
    return params, axes


# ---------------------------------------------------------------------------
# SSD core
# ---------------------------------------------------------------------------


def _segsum(x: jax.Array) -> jax.Array:
    """Lower-triangular cumulative sums: out[..., i, j] = sum_{j<t<=i} x[t]."""
    q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((q, q), bool), k=0)
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(
    x: jax.Array,   # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) — post-softplus
    a: jax.Array,   # (H,) — negative decay rates
    bm: jax.Array,  # (B, S, G, N)
    cm: jax.Array,  # (B, S, G, N)
    chunk: int = 256,
    h0: Optional[jax.Array] = None,  # (B, H, P, N)
) -> Tuple[jax.Array, jax.Array]:
    """Returns (y (B,S,H,P), final state (B,H,P,N))."""
    B, S, H, P = x.shape
    N = bm.shape[-1]
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0), (0, 0)))

    Q = chunk
    xf = x.astype(jnp.float32).reshape(B, nc, Q, H, P)
    dtf = dt.astype(jnp.float32).reshape(B, nc, Q, H)
    bf = bm.astype(jnp.float32).reshape(B, nc, Q, N_GROUPS, N)[..., 0, :]  # (B,nc,Q,N)
    cf = cm.astype(jnp.float32).reshape(B, nc, Q, N_GROUPS, N)[..., 0, :]

    da = dtf * a[None, None, None, :]  # (B, nc, Q, H) — negative
    da_cum = jnp.cumsum(da, axis=2)  # within chunk
    da_total = da_cum[:, :, -1:, :]  # (B, nc, 1, H)

    # ---- intra-chunk (quadratic dual form) ---------------------------------
    L = jnp.exp(_segsum(da.transpose(0, 1, 3, 2)))  # (B, nc, H, Q, Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", cf, bf)  # (B, nc, Q, Q)
    y_intra = jnp.einsum("bchqk,bcqk,bckh,bckhp->bcqhp", L, scores, dtf, xf)

    # ---- chunk states ------------------------------------------------------
    decay_to_end = jnp.exp(da_total - da_cum)  # (B, nc, Q, H)
    states = jnp.einsum("bcqn,bcqh,bcqhp->bchpn", bf, dtf * decay_to_end, xf)

    # ---- inter-chunk recurrence -------------------------------------------
    chunk_decay = jnp.exp(da_total[:, :, 0, :])  # (B, nc, H)

    def scan_step(h, xs):
        st, dec = xs  # (B,H,P,N), (B,H)
        h_new = h * dec[:, :, None, None] + st
        return h_new, h  # emit state *before* this chunk

    init = h0 if h0 is not None else jnp.zeros((B, H, P, N), jnp.float32)
    h_last, h_prevs = common_scan(
        scan_step,
        init.astype(jnp.float32),
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)),
    )
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4)  # (B, nc, H, P, N)

    decay_from_start = jnp.exp(da_cum)  # (B, nc, Q, H)
    y_inter = jnp.einsum(
        "bcqn,bcqh,bchpn->bcqhp", cf, decay_from_start, h_prevs
    )

    y = (y_intra + y_inter).reshape(B, nc * Q, H, P)[:, :S]
    return y, h_last


def ssd_decode_step(
    x: jax.Array,   # (B, H, P)
    dt: jax.Array,  # (B, H)
    a: jax.Array,   # (H,)
    bm: jax.Array,  # (B, N)
    cm: jax.Array,  # (B, N)
    h: jax.Array,   # (B, H, P, N) fp32
) -> Tuple[jax.Array, jax.Array]:
    da = jnp.exp(dt.astype(jnp.float32) * a[None, :])  # (B, H)
    dbx = jnp.einsum("bh,bn,bhp->bhpn", dt.astype(jnp.float32), bm.astype(jnp.float32), x.astype(jnp.float32))
    h_new = h * da[:, :, None, None] + dbx
    y = jnp.einsum("bn,bhpn->bhp", cm.astype(jnp.float32), h_new)
    return y, h_new


# ---------------------------------------------------------------------------
# Full mixer layer (conv frontend + SSD + gated output)
# ---------------------------------------------------------------------------


def _causal_conv(u: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over (B, S, C) with kernel (D_CONV, C)."""
    pad = w.shape[0] - 1
    uf = jnp.pad(u, ((0, 0), (pad, 0), (0, 0)))
    # unrolled depthwise conv: sum of shifted scaled copies (D_CONV is tiny)
    out = sum(
        uf[:, i : i + u.shape[1], :] * w[i][None, None, :] for i in range(w.shape[0])
    )
    return out + b[None, None, :]


def mamba_layer(
    lp: Dict[str, jax.Array],
    h: jax.Array,  # (B, S, D)
    ssm_heads: int,
    ssm_head_dim: int,
    d_state: int,
    chunk: int = 256,
    ssm_state: Optional[jax.Array] = None,   # (B,H,P,N) for decode
    conv_state: Optional[jax.Array] = None,  # (B, D_CONV-1, conv_dim)
    decode: bool = False,
) -> Tuple[jax.Array, Optional[jax.Array], Optional[jax.Array]]:
    """Returns (h_out, new_ssm_state, new_conv_state)."""
    B, S, D = h.shape
    H, P, N = ssm_heads, ssm_head_dim, d_state
    d_inner, conv_dim = mamba_dims(D, H, P, N)

    res = h
    x = rms_norm(h, lp["ln"])
    proj = x @ lp["in_proj"]  # (B, S, 2*d_inner + 2N + H)
    z, xbc, dt_raw = jnp.split(proj, [d_inner, d_inner + conv_dim], axis=-1)

    if decode:
        assert conv_state is not None
        window = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
        new_conv_state = window[:, 1:].astype(jnp.bfloat16)
        xbc_c = (
            jnp.einsum("bkc,kc->bc", window, lp["conv_w"]) + lp["conv_b"]
        )[:, None, :]
    else:
        xbc_c = _causal_conv(xbc, lp["conv_w"], lp["conv_b"])
        new_conv_state = xbc[:, -(D_CONV - 1):, :].astype(jnp.bfloat16) if S >= D_CONV - 1 else None
    xbc_c = jax.nn.silu(xbc_c)

    xs, bm, cm = jnp.split(xbc_c, [d_inner, d_inner + N_GROUPS * N], axis=-1)
    xs = xs.reshape(B, -1, H, P)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"][None, None, :])
    a = -jnp.exp(lp["a_log"])  # (H,) negative

    if decode:
        assert ssm_state is not None
        with jax.named_scope(scopes.SSD):
            y, new_state = ssd_decode_step(
                xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], ssm_state
            )
        y = y[:, None]  # (B, 1, H, P)
    else:
        bm4 = bm.reshape(B, -1, N_GROUPS, N)
        cm4 = cm.reshape(B, -1, N_GROUPS, N)
        with jax.named_scope(scopes.SSD):
            y, new_state = ssd_chunked(xs, dt, a, bm4, cm4, chunk=chunk, h0=ssm_state)

    y = y + xs.astype(jnp.float32) * lp["d_skip"][None, None, :, None]
    y = y.reshape(B, -1, d_inner).astype(h.dtype)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype), lp["norm"])
    out = res + (y @ lp["out_proj"]).astype(h.dtype)
    return out, new_state, new_conv_state
