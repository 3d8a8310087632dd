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


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------


def mamba_dims(d_model: int, ssm_heads: int, ssm_head_dim: int, d_state: int, groups: int = 1):
    """(d_inner, conv width): the conv runs over x and each group's B and C."""
    d_inner = ssm_heads * ssm_head_dim
    conv_dim = d_inner + 2 * groups * d_state
    return d_inner, conv_dim


def init_mamba_layer(
    key: jax.Array,
    d_model: int,
    ssm_heads: int,
    ssm_head_dim: int,
    d_state: int,
    dtype=jnp.bfloat16,
    groups: int = 1,
) -> Tuple[Dict[str, jax.Array], Dict[str, Tuple[str, ...]]]:
    H, P, N = ssm_heads, ssm_head_dim, d_state
    d_inner, conv_dim = mamba_dims(d_model, H, P, N, groups)
    ks = jax.random.split(key, 4)
    proj_out = 2 * d_inner + 2 * groups * N + H  # z, x, B, C, dt
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
#
# B and C come in ``G`` groups; head ``n`` of ``H`` reads group
# ``n // (H // G)``.  Heads are split as (G, H // G) wherever they meet B or C.


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
    G, N = bm.shape[-2:]
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bm = jnp.pad(bm, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cm = jnp.pad(cm, ((0, 0), (0, pad), (0, 0), (0, 0)))

    Q = chunk
    xf = x.astype(jnp.float32).reshape(B, nc, Q, G, H // G, P)
    dtf = dt.astype(jnp.float32).reshape(B, nc, Q, H)
    bf = bm.astype(jnp.float32).reshape(B, nc, Q, G, N)
    cf = cm.astype(jnp.float32).reshape(B, nc, Q, G, N)

    da = dtf * a[None, None, None, :]  # (B, nc, Q, H) — negative
    da_cum = jnp.cumsum(da, axis=2)  # within chunk
    da_total = da_cum[:, :, -1:, :]  # (B, nc, 1, H)
    grouped = lambda t: t.reshape(t.shape[:3] + (G, H // G))  # (B, nc, Q, G, H/G)

    # ---- intra-chunk (quadratic dual form) ---------------------------------
    L = jnp.exp(_segsum(da.transpose(0, 1, 3, 2)))  # (B, nc, H, Q, Q)
    L = L.reshape(B, nc, G, H // G, Q, Q)
    scores = jnp.einsum("bcqgn,bckgn->bcgqk", cf, bf)  # (B, nc, G, Q, Q)
    y_intra = jnp.einsum("bcghqk,bcgqk,bckgh,bckghp->bcqghp", L, scores, grouped(dtf), xf)

    # ---- chunk states ------------------------------------------------------
    decay_to_end = jnp.exp(da_total - da_cum)  # (B, nc, Q, H)
    states = jnp.einsum("bcqgn,bcqgh,bcqghp->bcghpn", bf, grouped(dtf * decay_to_end), xf)
    states = states.reshape(B, nc, H, P, N)

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
    h_prevs = h_prevs.transpose(1, 0, 2, 3, 4).reshape(B, nc, G, H // G, P, N)

    decay_from_start = jnp.exp(da_cum)  # (B, nc, Q, H)
    y_inter = jnp.einsum(
        "bcqgn,bcqgh,bcghpn->bcqghp", cf, grouped(decay_from_start), h_prevs
    )

    y = (y_intra + y_inter).reshape(B, nc * Q, H, P)[:, :S]
    return y, h_last


def ssd_decode_step(
    x: jax.Array,   # (B, H, P)
    dt: jax.Array,  # (B, H)
    a: jax.Array,   # (H,)
    bm: jax.Array,  # (B, G, N)
    cm: jax.Array,  # (B, G, N)
    h: jax.Array,   # (B, H, P, N) fp32
) -> Tuple[jax.Array, jax.Array]:
    """One step of the recurrence.  B and C are repeated to the heads (a
    few kilobytes), so the state keeps its own shape: splitting its head
    axis into groups made the compiler store it in a tiling that moved
    several times its bytes."""
    heads_per_group = x.shape[1] // bm.shape[1]
    bh = jnp.repeat(bm.astype(jnp.float32), heads_per_group, axis=1)  # (B, H, N)
    ch = jnp.repeat(cm.astype(jnp.float32), heads_per_group, axis=1)
    dt = dt.astype(jnp.float32)
    da = jnp.exp(dt * a[None, :])  # (B, H)
    dbx = (dt[:, :, None] * x.astype(jnp.float32))[..., None] * bh[:, :, None, :]
    h_new = h * da[:, :, None, None] + dbx
    y = jnp.sum(h_new * ch[:, :, None, :], axis=-1)  # (B, H, P)
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
    groups: int = 1,
    extra: Optional[jax.Array] = None,  # (B, S, D), added to the input, not the residual
    eps: float = 1e-6,  # of both norms
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (h_out, new_ssm_state, new_conv_state).

    ``h + Mamba2(RMSNorm(h + extra))``: the norm, projections, conv and
    gated norm are the ``mamba`` scope, the scan the ``ssd`` scope.  The
    gated output is normalized over each group's ``d_inner / groups``
    channels.  The new conv state is the last ``D_CONV - 1`` conv inputs,
    zeros before the first."""
    B, S, D = h.shape
    H, P, N, G = ssm_heads, ssm_head_dim, d_state, groups
    d_inner, conv_dim = mamba_dims(D, H, P, N, G)

    with jax.named_scope(scopes.MAMBA):
        x = rms_norm(h if extra is None else h + extra, lp["ln"], eps)
        proj = x @ lp["in_proj"]  # (B, S, 2*d_inner + 2GN + H)
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
            tail = jnp.pad(xbc, ((0, 0), (max(0, D_CONV - 1 - S), 0), (0, 0)))
            new_conv_state = tail[:, -(D_CONV - 1):].astype(jnp.bfloat16)
        xbc_c = jax.nn.silu(xbc_c)

        xs, bm, cm = jnp.split(xbc_c, [d_inner, d_inner + G * N], axis=-1)
        xs = xs.reshape(B, -1, H, P)
        bm = bm.reshape(B, -1, G, N)
        cm = cm.reshape(B, -1, G, N)
        dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + lp["dt_bias"][None, None, :])
        a = -jnp.exp(lp["a_log"])  # (H,) negative

    with jax.named_scope(scopes.SSD):
        if decode:
            assert ssm_state is not None
            y, new_state = ssd_decode_step(
                xs[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], ssm_state
            )
            y = y[:, None]  # (B, 1, H, P)
        else:
            y, new_state = ssd_chunked(xs, dt, a, bm, cm, chunk=chunk, h0=ssm_state)

    with jax.named_scope(scopes.MAMBA):
        y = y + xs.astype(jnp.float32) * lp["d_skip"][None, None, :, None]
        y = y.reshape(B, -1, d_inner).astype(h.dtype)
        y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
        y = rms_norm(y.reshape(B, -1, G, d_inner // G), lp["norm"].reshape(G, -1), eps)
        out = h + (y.reshape(B, -1, d_inner) @ lp["out_proj"]).astype(h.dtype)
    return out, new_state, new_conv_state
