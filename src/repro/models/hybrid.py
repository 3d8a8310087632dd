"""Zamba2 hybrid: a Mamba2 stack fed, at chosen layers, by shared attention blocks.

arXiv:2411.15242, as Hugging Face's ``modeling_zamba2.py`` computes it.
With ``e`` the embedding output and ``h`` the residual stream, layer ``i`` is

    h <- h + Mamba2_i(RMSNorm_i(h + t))

with ``t = 0`` unless ``i`` is the ``j``-th entry of ``cfg.hybrid_layer_ids``.
There shared block ``b = j % cfg.num_mem_blocks`` computes ``t`` from
``[h ; e]``, 2·D wide, with no residual of its own:

    x = RMSNorm_b([h ; e]);  q, k, v = x Wq_b, x Wk_b, x Wv_b  (rotary on q, k)
    o = softmax(q k^T / sqrt(Dh / 2)) v Wo_b                    (causal)
    u = RMSNorm_b,ff(o);  g, up = split(u W_gu_b + (u A_j) B_j)
    t = ((gelu(g) * up) W_down_b) Lin_j                         (erf GELU)

A block's weights serve every application of it; the MLP adapter
``(A_j, B_j)`` and the output linear ``Lin_j`` are application ``j``'s own,
and so is its KV cache: caches are stacked over applications.

The layers between two applications form a segment, run by one loop over
their stacked weights; a segment's SSM and conv states are one array each,
which its loop carries and rewrites one layer's slice at a time (in place
when donated).  Decode reads each application's cache where it is stored
and writes the token's rows after the last layer
(:func:`transformer.write_token_rows`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from . import scopes
from .attention import attention, attention_decode_at
from .common import scan as common_scan, apply_rope, dense_init, rms_norm, trunc_normal
from .mamba2 import D_CONV, init_mamba_layer, mamba_dims, mamba_layer
from .transformer import ModelConfig, lm_loss, write_token_rows

Pytree = Any

#: Zamba2's ``rms_norm_eps``, for every norm of the family
NORM_EPS = 1e-5


def segments(cfg: ModelConfig) -> List[Tuple[Optional[int], int, int]]:
    """``(j, lo, hi)``: layers ``lo .. hi - 1``, the first of them fed by
    application ``j`` of a shared block (``None`` before the first)."""
    ids, L = list(cfg.hybrid_layer_ids), cfg.n_layers
    if ids != sorted(set(ids)) or (ids and not 0 <= ids[0] <= ids[-1] < L):
        raise ValueError(f"hybrid_layer_ids must rise within [0, {L}): {ids}")
    out = [(None, 0, ids[0] if ids else L)] if not ids or ids[0] > 0 else []
    return out + [(j, lo, hi) for j, (lo, hi) in enumerate(zip(ids, ids[1:] + [L]))]


def attention_scale(cfg: ModelConfig) -> float:
    """Zamba2 scales scores by ``(head_dim / 2) ** -0.5``: its heads are 2·D/H
    wide, its scale that of a D/H-wide head."""
    return (cfg.dh / 2) ** -0.5


def init_params(cfg: ModelConfig, key: jax.Array) -> Tuple[Pytree, Pytree]:
    """Weights from ``key``, split in this order: the embedding (0.02), the
    Mamba2 layers (one key each), the shared blocks (one key each, split
    again per matrix in the order of ``block``'s keys), the applications
    (likewise); each group stacked on axis 0.  Projections are truncated
    normals at ``1/sqrt(d_in)``, norm scales zero."""
    k_embed, k_mamba, k_blocks, k_apps = jax.random.split(key, 4)
    L, D, V, dt = cfg.n_layers, cfg.d_model, cfg.vocab, cfg.dtype
    Hq, Hkv, Dh, F, r = cfg.n_heads, cfg.n_kv_heads, cfg.dh, cfg.d_ff, cfg.adapter_rank

    def init_one(k):
        return init_mamba_layer(k, D, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                                dtype=dt, groups=cfg.ssm_groups)

    mamba = jax.vmap(lambda k: init_one(k)[0])(jax.random.split(k_mamba, L))
    m_axes = init_one(k_mamba)[1]

    def block(k):
        ks = jax.random.split(k, 6)
        return {
            "ln_attn": jnp.zeros((2 * D,), dt),
            "wq": dense_init(ks[0], 2 * D, Hq * Dh, dt),
            "wk": dense_init(ks[1], 2 * D, Hkv * Dh, dt),
            "wv": dense_init(ks[2], 2 * D, Hkv * Dh, dt),
            "wo": dense_init(ks[3], Hq * Dh, D, dt),
            "ln_ff": jnp.zeros((D,), dt),
            "w_gate_up": dense_init(ks[4], D, 2 * F, dt),
            "w_down": dense_init(ks[5], F, D, dt),
        }

    block_axes = {
        "ln_attn": ("embed2",), "wq": ("embed2", "heads"), "wk": ("embed2", "heads"),
        "wv": ("embed2", "heads"), "wo": ("heads", "embed"), "ln_ff": ("embed",),
        "w_gate_up": ("embed", "ff"), "w_down": ("ff", "embed"),
    }

    def app(k):
        ks = jax.random.split(k, 3)
        p = {"linear": dense_init(ks[2], D, D, dt)}
        if r:
            p["adapter_in"] = dense_init(ks[0], D, r, dt)
            p["adapter_out"] = dense_init(ks[1], r, 2 * F, dt)
        return p

    app_axes = {"linear": ("embed", "embed2")}
    if r:
        app_axes.update(adapter_in=("embed", None), adapter_out=(None, "ff"))

    stacked = lambda ax: {k: ("layers",) + v for k, v in ax.items()}
    params = {
        "embed": trunc_normal(k_embed, (V, D), std=0.02, dtype=dt),
        "mamba": mamba,
        "blocks": jax.vmap(block)(jax.random.split(k_blocks, cfg.num_mem_blocks)),
        "apps": jax.vmap(app)(jax.random.split(k_apps, len(cfg.hybrid_layer_ids))),
        "final_ln": jnp.zeros((D,), dt),
    }
    axes = {
        "embed": ("vocab", "embed_tbl"),
        "mamba": stacked(m_axes),
        "blocks": stacked(block_axes),
        "apps": stacked(app_axes),
        "final_ln": ("embed",),
    }
    return params, axes


def init_state(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Zero decode state: per-application KV caches, and the SSM (f32) and
    conv states of each run of layers between two applications (one array
    per entry of :func:`segments`, layers stacked on axis 0)."""
    _, conv_dim = mamba_dims(cfg.d_model, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                             cfg.ssm_groups)
    kv = (len(cfg.hybrid_layer_ids), batch, max_len, cfg.n_kv_heads, cfg.dh)
    runs = [hi - lo for _, lo, hi in segments(cfg)]
    return {
        "kv": (jnp.zeros(kv, cfg.dtype), jnp.zeros(kv, cfg.dtype)),
        "ssm": tuple(jnp.zeros((n, batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state),
                               jnp.float32) for n in runs),
        "conv": tuple(jnp.zeros((n, batch, D_CONV - 1, conv_dim), jnp.bfloat16) for n in runs),
    }


# ---------------------------------------------------------------------------
# The shared block
# ---------------------------------------------------------------------------


def _shared_qkv(cfg: ModelConfig, bp, h, e, positions):
    B, S, _ = h.shape
    with jax.named_scope(scopes.QKV):
        x = rms_norm(jnp.concatenate([h, e], axis=-1), bp["ln_attn"], NORM_EPS)
        q = (x @ bp["wq"]).reshape(B, S, cfg.n_heads, cfg.dh)
        k = (x @ bp["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.dh)
        v = (x @ bp["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.dh)
        return (apply_rope(q, positions, cfg.rope_theta),
                apply_rope(k, positions, cfg.rope_theta), v)


def _shared_mlp(cfg: ModelConfig, bp, ap, o):
    """``t`` from the attention's output ``o`` (B, S, D)."""
    with jax.named_scope(scopes.MLP):
        u = rms_norm(o, bp["ln_ff"], NORM_EPS)
        gu = u @ bp["w_gate_up"]
        if "adapter_in" in ap:
            gu = gu + (u @ ap["adapter_in"]) @ ap["adapter_out"]
        g, up = jnp.split(gu, 2, axis=-1)
        return ((jax.nn.gelu(g, approximate=False) * up) @ bp["w_down"]) @ ap["linear"]


def _block_and_app(cfg: ModelConfig, params, j):
    """Application ``j``'s block weights and its own."""
    pick = lambda tree, n: jax.tree.map(lambda w: w[n], tree)
    return pick(params["blocks"], j % cfg.num_mem_blocks), pick(params["apps"], j)


# ---------------------------------------------------------------------------
# Mamba2 layers
# ---------------------------------------------------------------------------


def _mamba_layers(cfg: ModelConfig, params, h, t, lo: int, hi: int, states, decode: bool,
                  remat: str = "none"):
    """Layers ``lo .. hi - 1`` of one segment, ``t`` (or None) added to the first's
    input.  ``states`` (the segment's SSM and conv states, or None) ride in
    the loop's carry, are read in decode, and are rewritten one layer at a
    time, in place when donated; returns (h, states)."""
    def body(carry, n):
        h, t, states = carry
        lp = jax.tree.map(lambda w: w[lo + n], params["mamba"])
        ssm_n = conv_n = None
        if decode:
            with jax.named_scope(scopes.SSD):
                ssm_n = states[0][n]
            with jax.named_scope(scopes.MAMBA):
                conv_n = states[1][n]
        h, ssm_new, conv_new = mamba_layer(
            lp, h, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, chunk=cfg.ssm_chunk,
            ssm_state=ssm_n, conv_state=conv_n, decode=decode, groups=cfg.ssm_groups, extra=t,
            eps=NORM_EPS,
        )
        if states is not None:
            with jax.named_scope(scopes.SSD):
                ssm = jax.lax.dynamic_update_index_in_dim(states[0], ssm_new, n, 0)
            with jax.named_scope(scopes.MAMBA):
                conv = jax.lax.dynamic_update_index_in_dim(states[1], conv_new, n, 0)
            states = (ssm, conv)
        return (h, jnp.zeros_like(t), states), None

    if remat in ("dots", "full"):
        body = jax.checkpoint(body, prevent_cse=False)
    t = jnp.zeros_like(h) if t is None else t.astype(h.dtype)
    (h, _, states), _ = common_scan(body, (h, t, states), jnp.arange(hi - lo))
    return h, states


# ---------------------------------------------------------------------------
# Step functions
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: Pytree,
    tokens: jax.Array,  # (B, S) int32, from position 0
    attn_impl: str = "chunked",
    remat: str = "none",
    max_len: Optional[int] = None,
) -> Tuple[jax.Array, Optional[Dict[str, Any]]]:
    """Final hidden states (B, S, D) and, with ``max_len`` (prefill), the
    decode state as :func:`init_state` lays it out: each application's KV
    cache holding the sequence, each layer's SSM and conv state after it."""
    B, S = tokens.shape
    e = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    state = init_state(cfg, B, max_len) if max_len is not None else None
    caches = list(state["kv"]) if state is not None else None
    ssm, conv = [], []

    h = e
    for seg, (j, lo, hi) in enumerate(segments(cfg)):
        t = None
        if j is not None:
            bp, ap = _block_and_app(cfg, params, j)
            q, k, v = _shared_qkv(cfg, bp, h, e, positions)
            if caches is not None:
                with jax.named_scope(scopes.KV_WRITE):
                    caches = [jax.lax.dynamic_update_slice(c, new.astype(c.dtype)[None],
                                                           (j, 0, 0, 0, 0))
                              for c, new in zip(caches, (k, v))]
            with jax.named_scope(scopes.ATTENTION):
                o = attention(q, k, v, positions, positions, impl=attn_impl,
                              scale=attention_scale(cfg))
                o = o.reshape(B, S, -1) @ bp["wo"]
            t = _shared_mlp(cfg, bp, ap, o)
        states = (state["ssm"][seg], state["conv"][seg]) if state is not None else None
        h, states = _mamba_layers(cfg, params, h, t, lo, hi, states, decode=False, remat=remat)
        if states is not None:
            ssm.append(states[0])
            conv.append(states[1])
    h = rms_norm(h, params["final_ln"], NORM_EPS)
    if state is None:
        return h, None
    return h, {"kv": tuple(caches), "ssm": tuple(ssm), "conv": tuple(conv)}


def decode(
    cfg: ModelConfig,
    params: Pytree,
    tokens: jax.Array,  # (B, 1) int32
    positions: jax.Array,  # (B,) each token's position
    state: Dict[str, Any],  # as init_state gives it
) -> Tuple[jax.Array, Dict[str, Any]]:
    """One token per sequence against the decode state; returns (final hidden
    states (B, 1, D), the state with the token taken in).

    Each segment's layers run in one loop that carries that segment's own
    SSM and conv states: one array per loop keeps the stored layout in
    every loop, where one stacked array made the compiler convert it
    between loops.  Each application reads its cache where it is stored;
    its new rows are written after the last layer."""
    B, S = tokens.shape
    if S != 1:
        raise ValueError(f"decode takes one token per sequence, got {S}")
    e = params["embed"][tokens].astype(cfg.dtype)
    ck_all, cv_all = state["kv"]
    rows: Tuple[List[jax.Array], List[jax.Array]] = ([], [])
    ssm, conv = [], []

    h = e
    for seg, (j, lo, hi) in enumerate(segments(cfg)):
        t = None
        if j is not None:
            bp, ap = _block_and_app(cfg, params, j)
            q, k, v = _shared_qkv(cfg, bp, h, e, positions[:, None])
            k, v = k.astype(ck_all.dtype), v.astype(cv_all.dtype)
            with jax.named_scope(scopes.ATTENTION):
                o = attention_decode_at(q, k, v, ck_all, cv_all, j, positions,
                                        scale=attention_scale(cfg))
                o = o.reshape(B, 1, -1) @ bp["wo"]
            t = _shared_mlp(cfg, bp, ap, o)
            rows[0].append(k)
            rows[1].append(v)
        h, (s_new, c_new) = _mamba_layers(cfg, params, h, t, lo, hi,
                                          (state["ssm"][seg], state["conv"][seg]), decode=True)
        ssm.append(s_new)
        conv.append(c_new)

    kv = (ck_all, cv_all)
    if rows[0]:
        kv = write_token_rows(kv, (jnp.stack(rows[0]), jnp.stack(rows[1])), positions)
    h = rms_norm(h, params["final_ln"], NORM_EPS)
    return h, {"kv": kv, "ssm": tuple(ssm), "conv": tuple(conv)}


def lm_head_loss(cfg, params, h, targets, chunk: int = 512):
    """Chunked cross-entropy against the tied embedding (Zamba2 ties)."""
    tied = dataclasses.replace(cfg, tie_embeddings=True)
    return lm_loss(tied, {"embed": params["embed"]}, h, targets, chunk=chunk)
