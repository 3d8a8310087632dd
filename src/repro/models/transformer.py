"""Decoder-only transformer family: dense, MoE, VLM-backbone.

One parameterized implementation covers stablelm-3b, gemma3-1b, qwen2-7b,
granite-8b, qwen2-moe-a2.7b, llama4-scout and the qwen2-vl-2b backbone:

* GQA attention with optional QKV bias, per-layer sliding-window /
  chunked-attention masks (gemma3 5:1 local:global, llama4 iRoPE), per-layer
  RoPE enable/theta, M-RoPE for the VLM;
* dense SwiGLU or MoE FFN (shared + routed experts, top-k, capacity-based
  scatter dispatch so compiled FLOPs reflect *active* experts only);
* layer stacks are scanned (``jax.lax.scan``) over stacked parameters:
  HLO size is O(1) in depth;
* three step flavours: ``train`` (full seq), ``prefill`` (returns KV cache),
  ``decode`` (one token against the cache, which it reads in place and
  writes only at the token's rows).

Parameters are plain pytrees; a parallel *logical-axes* pytree drives
sharding (:mod:`repro.sharding`).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import scopes
from .attention import attention, attention_decode_at
from .common import apply_mrope, apply_rope, rms_norm, swiglu, trunc_normal

Pytree = Any

#: sentinel "no restriction" for traced window/chunk masks inside scan
BIG = 1 << 30


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    d_ff_shared: int = 0
    shared_gate: bool = False       # qwen2-moe: sigmoid gate on shared expert
    capacity_factor: float = 1.25
    norm_topk: bool = True


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | vlm | audio | ssm | hybrid
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None
    qkv_bias: bool = False
    tie_embeddings: bool = False
    rope_theta: float = 10_000.0
    # attention pattern: period p means layer i is GLOBAL iff (i+1) % p == 0;
    # other layers use `window` (sliding) or `attn_chunk` (chunked)
    global_period: int = 1           # 1 => every layer global
    window: Optional[int] = None
    attn_chunk: Optional[int] = None
    nope_on_global: bool = False     # llama4 iRoPE: no RoPE on global layers
    local_rope_theta: Optional[float] = None  # gemma3: 10k local / 1M global
    moe: Optional[MoEConfig] = None
    mrope: bool = False              # qwen2-vl M-RoPE
    # ssm / hybrid knobs live in mamba2.py / hybrid.py but are carried here
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_chunk: int = 256
    ssm_groups: int = 1              # B/C groups; head n reads group n // (H/G)
    # hybrid (Zamba2): the layers that a shared attention block feeds, the
    # number of shared blocks (used in turn), and each application's rank of
    # the MLP adapter
    hybrid_layer_ids: Tuple[int, ...] = ()
    num_mem_blocks: int = 1
    adapter_rank: int = 0
    dtype: Any = jnp.bfloat16
    # free-text notes on the architecture
    notes: str = ""

    @property
    def dh(self) -> int:
        if self.head_dim:
            return self.head_dim
        return self.d_model // self.n_heads if self.n_heads else 0

    def layer_kinds(self) -> jnp.ndarray:
        """0 = local/chunked layer, 1 = global layer."""
        idx = jnp.arange(self.n_layers)
        if self.global_period <= 1:
            return jnp.ones((self.n_layers,), jnp.int32)
        return ((idx + 1) % self.global_period == 0).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Parameter init (+ logical axes)
# ---------------------------------------------------------------------------

A = lambda *names: tuple(names)  # logical-axes shorthand


def init_params(cfg: ModelConfig, key: jax.Array) -> Tuple[Pytree, Pytree]:
    """Returns (params, logical_axes) with layer-stacked weights."""
    keys = jax.random.split(key, 16)
    L, D, V = cfg.n_layers, cfg.d_model, cfg.vocab
    Hq, Hkv, Dh, F = cfg.n_heads, cfg.n_kv_heads, cfg.dh, cfg.d_ff
    dt = cfg.dtype

    def stack(initializer, k, *shape_axes):
        shape, axes = zip(*shape_axes)
        ks = jax.random.split(k, L)
        w = jax.vmap(lambda kk: initializer(kk, shape))(ks)
        return w, A("layers", *axes)

    def sdense(k, d_in, d_out, ax_in, ax_out):
        init = lambda kk, shape: trunc_normal(kk, shape, std=1.0 / math.sqrt(d_in), dtype=dt)
        return stack(init, k, (d_in, ax_in), (d_out, ax_out))

    params: Dict[str, Any] = {}
    axes: Dict[str, Any] = {}

    # vocab matrices keep their D dim replicated ("embed_tbl"): FSDP-sharding
    # it makes the LM head contract over a data-sharded dim, and GSPMD then
    # all-reduces (B,S,V) logits over the data axis — gigabytes per step
    params["embed"] = trunc_normal(keys[0], (V, D), std=0.02, dtype=dt)
    axes["embed"] = A("vocab", "embed_tbl")

    layers: Dict[str, Any] = {}
    lax_: Dict[str, Any] = {}
    layers["ln1"], lax_["ln1"] = stack(
        lambda kk, s: jnp.zeros(s, dt), keys[1], (D, "embed")
    )
    layers["ln2"], lax_["ln2"] = stack(
        lambda kk, s: jnp.zeros(s, dt), keys[2], (D, "embed")
    )
    layers["wq"], lax_["wq"] = sdense(keys[3], D, Hq * Dh, "embed", "heads")
    layers["wk"], lax_["wk"] = sdense(keys[4], D, Hkv * Dh, "embed", "heads")
    layers["wv"], lax_["wv"] = sdense(keys[5], D, Hkv * Dh, "embed", "heads")
    layers["wo"], lax_["wo"] = sdense(keys[6], Hq * Dh, D, "heads", "embed")
    if cfg.qkv_bias:
        for nm, width in (("bq", Hq * Dh), ("bk", Hkv * Dh), ("bv", Hkv * Dh)):
            layers[nm], lax_[nm] = stack(
                lambda kk, s: jnp.zeros(s, dt), keys[7], (width, "heads")
            )
    if cfg.moe is None:
        layers["w_gate"], lax_["w_gate"] = sdense(keys[8], D, F, "embed", "ff")
        layers["w_up"], lax_["w_up"] = sdense(keys[9], D, F, "embed", "ff")
        layers["w_down"], lax_["w_down"] = sdense(keys[10], F, D, "ff", "embed")
    else:
        m = cfg.moe
        E, Fe = m.n_experts, m.d_ff_expert
        layers["router"], lax_["router"] = sdense(keys[8], D, E, "embed", "expert_dim")

        def estack(k, d_in, d_out, ax_in, ax_out):
            init = lambda kk, shape: trunc_normal(
                kk, shape, std=1.0 / math.sqrt(d_in), dtype=dt
            )
            ks = jax.random.split(k, L)
            w = jax.vmap(lambda kk: init(kk, (E, d_in, d_out)))(ks)
            return w, A("layers", "expert", ax_in, ax_out)

        layers["we_gate"], lax_["we_gate"] = estack(keys[9], D, Fe, "embed", "ff_expert")
        layers["we_up"], lax_["we_up"] = estack(keys[10], D, Fe, "embed", "ff_expert")
        layers["we_down"], lax_["we_down"] = estack(keys[11], Fe, D, "ff_expert", "embed")
        if m.n_shared:
            Fs = m.d_ff_shared
            layers["ws_gate"], lax_["ws_gate"] = sdense(keys[12], D, Fs, "embed", "ff")
            layers["ws_up"], lax_["ws_up"] = sdense(keys[13], D, Fs, "embed", "ff")
            layers["ws_down"], lax_["ws_down"] = sdense(keys[14], Fs, D, "ff", "embed")
            if m.shared_gate:
                layers["ws_g"], lax_["ws_g"] = sdense(keys[15], D, 1, "embed", None)
    params["layers"] = layers
    axes["layers"] = lax_

    params["final_ln"] = jnp.zeros((D,), dt)
    axes["final_ln"] = A("embed")
    if not cfg.tie_embeddings:
        params["lm_head"] = trunc_normal(keys[7], (D, V), std=1.0 / math.sqrt(D), dtype=dt)
        axes["lm_head"] = A("embed_tbl", "vocab")
    if cfg.family == "vlm":
        params["patch_proj"] = trunc_normal(keys[6], (D, D), std=1.0 / math.sqrt(D), dtype=dt)
        axes["patch_proj"] = A("embed", "embed2")
    return params, axes


# ---------------------------------------------------------------------------
# MoE dispatch (capacity-based scatter; FLOPs = active experts only)
# ---------------------------------------------------------------------------


def _moe_dense_exact(x, lp, m, gate, expert):
    """Exact no-drop MoE for small T: every expert runs on every token and
    the top-k mask selects.  O(T*E*D*F) — only used for decode-sized T."""
    T, D = x.shape
    h = swiglu(
        jnp.einsum("td,edf->tef", x, lp["we_gate"]),
        jnp.einsum("td,edf->tef", x, lp["we_up"]),
    )
    y_all = jnp.einsum("tef,efd->ted", h, lp["we_down"])  # (T, E, D)
    onehot = jax.nn.one_hot(expert, m.n_experts, dtype=y_all.dtype)  # (T,k,E)
    w = (onehot * gate[..., None].astype(y_all.dtype)).sum(axis=1)  # (T, E)
    return jnp.einsum("ted,te->td", y_all, w)


def moe_ffn(
    x: jax.Array,
    lp: Dict[str, jax.Array],
    m: MoEConfig,
    dense_path_max_tokens: int = 256,
) -> jax.Array:
    """x: (T, D) -> (T, D).  Sort-based position assignment + scatter into an
    (E, C, D) expert buffer; dropped tokens (over capacity) contribute 0.
    Decode-sized inputs (T <= dense_path_max_tokens) take the exact path."""
    T, D = x.shape
    E, k = m.n_experts, m.top_k
    C = max(1, int(math.ceil(T * k / E * m.capacity_factor)))

    logits = (x @ lp["router"]).astype(jnp.float32)  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, expert = jax.lax.top_k(probs, k)  # (T, k)
    if m.norm_topk:
        gate = gate / jnp.maximum(jnp.sum(gate, axis=-1, keepdims=True), 1e-9)

    if T <= dense_path_max_tokens:
        y = _moe_dense_exact(x, lp, m, gate, expert)
        if m.n_shared:
            ys = swiglu(x @ lp["ws_gate"], x @ lp["ws_up"]) @ lp["ws_down"]
            if m.shared_gate:
                ys = ys * jax.nn.sigmoid((x @ lp["ws_g"]).astype(jnp.float32)).astype(ys.dtype)
            y = y + ys
        return y

    flat_e = expert.reshape(-1)  # (T*k,)
    # position of each assignment within its expert via stable sort
    perm = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[perm]
    idx = jnp.arange(T * k)
    is_start = jnp.concatenate([jnp.ones((1,), bool), sorted_e[1:] != sorted_e[:-1]])
    group_start = jax.lax.associative_scan(jnp.maximum, jnp.where(is_start, idx, 0))
    pos_sorted = idx - group_start
    inv = jnp.argsort(perm, stable=True)
    pos = pos_sorted[inv]  # (T*k,) position within expert

    keep = pos < C
    dest = jnp.where(keep, flat_e * C + pos, E * C)  # drop bucket at E*C
    x_rep = jnp.repeat(x, k, axis=0)  # (T*k, D)
    buf = jnp.zeros((E * C + 1, D), x.dtype).at[dest].add(x_rep)
    xe = buf[: E * C].reshape(E, C, D)

    h = swiglu(
        jnp.einsum("ecd,edf->ecf", xe, lp["we_gate"]),
        jnp.einsum("ecd,edf->ecf", xe, lp["we_up"]),
    )
    ye = jnp.einsum("ecf,efd->ecd", h, lp["we_down"]).reshape(E * C, D)
    ye = jnp.concatenate([ye, jnp.zeros((1, D), ye.dtype)], axis=0)
    y = ye[dest] * (gate.reshape(-1, 1).astype(ye.dtype)) * keep[:, None]
    y = y.reshape(T, k, D).sum(axis=1)

    if m.n_shared:
        ys = swiglu(x @ lp["ws_gate"], x @ lp["ws_up"]) @ lp["ws_down"]
        if m.shared_gate:
            ys = ys * jax.nn.sigmoid((x @ lp["ws_g"]).astype(jnp.float32)).astype(ys.dtype)
        y = y + ys
    return y


# ---------------------------------------------------------------------------
# Transformer block + step functions
# ---------------------------------------------------------------------------


def _qkv(
    h: jax.Array, lp: Dict[str, jax.Array], cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, D = h.shape
    Hq, Hkv, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    q = h @ lp["wq"]
    k = h @ lp["wk"]
    v = h @ lp["wv"]
    if cfg.qkv_bias:
        q = q + lp["bq"]
        k = k + lp["bk"]
        v = v + lp["bv"]
    return (
        q.reshape(B, S, Hq, Dh),
        k.reshape(B, S, Hkv, Dh),
        v.reshape(B, S, Hkv, Dh),
    )


def _rope(cfg: ModelConfig, x, positions, kind, mrope_positions=None):
    if cfg.mrope and mrope_positions is not None:
        return apply_mrope(x, mrope_positions, theta=cfg.rope_theta)
    theta = cfg.rope_theta
    if cfg.local_rope_theta is not None:
        # gemma3: local layers use the local theta; kind is traced
        pos_local = apply_rope(x, positions, cfg.local_rope_theta)
        pos_global = apply_rope(x, positions, theta)
        return jnp.where(kind[..., None, None, None] > 0, pos_global, pos_local)
    if cfg.nope_on_global:
        roped = apply_rope(x, positions, theta)
        return jnp.where(kind[..., None, None, None] > 0, x, roped)
    return apply_rope(x, positions, theta)


def _mask_params(cfg: ModelConfig, kind: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-layer (window, chunk) as traced ints (BIG = unrestricted)."""
    window = jnp.where(kind > 0, BIG, cfg.window or BIG)
    chunk = jnp.where(kind > 0, BIG, cfg.attn_chunk or BIG)
    return window, chunk


def block(
    cfg: ModelConfig,
    h: jax.Array,
    lp: Dict[str, jax.Array],
    kind: jax.Array,
    positions: jax.Array,
    attn_impl: str,
    kv_cache: Optional[Tuple[jax.Array, jax.Array]] = None,
    cache_positions: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """One pre-norm transformer block; returns (h, new_kv)."""
    with jax.named_scope(scopes.QKV):
        x = rms_norm(h, lp["ln1"])
        q, k, v = _qkv(x, lp, cfg)
        q = _rope(cfg, q, positions, kind, mrope_positions)
        k = _rope(cfg, k, positions, kind, mrope_positions)

    if kv_cache is not None:
        ck, cv = kv_cache  # (B, Skv, Hkv, Dh)
        # prefill: insert the tokens at their positions, attend over the cache
        with jax.named_scope(scopes.KV_WRITE):
            upd = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice(c, u, (p, 0, 0)))
            ck = upd(ck, k.astype(ck.dtype), positions[:, 0])
            cv = upd(cv, v.astype(cv.dtype), positions[:, 0])
        k_att, v_att = ck, cv
        kv_positions = cache_positions
        new_cache = (ck, cv)
    else:
        k_att, v_att = k, v
        kv_positions = positions
        new_cache = None

    window, chunk = _mask_params(cfg, kind)
    with jax.named_scope(scopes.ATTENTION):
        o = attention(
            q, k_att, v_att, positions, kv_positions,
            impl=attn_impl, window=window, chunk_attn=chunk,
        )
        B, S = h.shape[:2]
        h = h + (o.reshape(B, S, -1) @ lp["wo"]).astype(h.dtype)
    return _mlp(cfg, h, lp), new_cache


def _mlp(cfg: ModelConfig, h: jax.Array, lp: Dict[str, jax.Array]) -> jax.Array:
    with jax.named_scope(scopes.MLP):
        x = rms_norm(h, lp["ln2"])
        if cfg.moe is None:
            y = swiglu(x @ lp["w_gate"], x @ lp["w_up"]) @ lp["w_down"]
        else:
            y = moe_ffn(x.reshape(-1, cfg.d_model), lp, cfg.moe).reshape(x.shape)
        return h + y.astype(h.dtype)


def decode_block(
    cfg: ModelConfig,
    h: jax.Array,  # (B, 1, D)
    lp: Dict[str, jax.Array],
    kind: jax.Array,
    positions: jax.Array,  # (B, 1)
    caches: Tuple[jax.Array, jax.Array],  # (L, B, Skv, Hkv, Dh) x2, read only
    i: jax.Array,  # this layer's index into them
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """:func:`block` for one token per sequence against layer ``i`` of the
    stacked caches, which it only reads; returns (h, the token's (k, v) in
    the caches' dtype) for the caller to write."""
    ck_all, cv_all = caches
    with jax.named_scope(scopes.QKV):
        x = rms_norm(h, lp["ln1"])
        q, k, v = _qkv(x, lp, cfg)
        q = _rope(cfg, q, positions, kind)
        k = _rope(cfg, k, positions, kind).astype(ck_all.dtype)
        v = v.astype(cv_all.dtype)

    window, chunk = _mask_params(cfg, kind)
    with jax.named_scope(scopes.ATTENTION):
        o = attention_decode_at(q, k, v, ck_all, cv_all, i, positions[:, 0], window=window,
                                chunk_attn=chunk)
        h = h + (o.reshape(h.shape[0], 1, -1) @ lp["wo"]).astype(h.dtype)
    return _mlp(cfg, h, lp), (k, v)


def _split_moe_keys(cfg: ModelConfig, lp: Dict[str, jax.Array]):
    return lp


def forward(
    cfg: ModelConfig,
    params: Pytree,
    tokens: jax.Array,  # (B, S) int32
    attn_impl: str = "chunked",
    remat: str = "none",  # none | dots | full
    patch_embeds: Optional[jax.Array] = None,
    mrope_positions: Optional[jax.Array] = None,
    kv_caches: Optional[Tuple[jax.Array, jax.Array]] = None,  # (L,B,Skv,Hkv,Dh) x2
    cache_positions: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """The sequences from position 0 (training, prefill); returns (final
    hidden states (B,S,D), stacked new KV caches or None)."""
    B, S = tokens.shape
    h = params["embed"][tokens].astype(cfg.dtype)
    if cfg.family == "vlm" and patch_embeds is not None:
        # frontend stub: precomputed patch embeddings occupy the prefix
        P = patch_embeds.shape[1]
        proj = (patch_embeds.astype(cfg.dtype) @ params["patch_proj"]).astype(cfg.dtype)
        h = jnp.concatenate([proj, h[:, P:]], axis=1)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))

    kinds = cfg.layer_kinds()

    def scan_body(carry, xs):
        if kv_caches is not None:
            # the stacked caches ride in the carry and are rewritten one layer
            # slice at a time, so a donated cache is updated in place
            h, ck_all, cv_all = carry
            lp, kind, i = xs
            with jax.named_scope(scopes.KV_CARRY):
                layer_kv = (ck_all[i], cv_all[i])
            h, (ck, cv) = block(
                cfg, h, lp, kind, positions, attn_impl,
                kv_cache=layer_kv, cache_positions=cache_positions,
                mrope_positions=mrope_positions,
            )
            with jax.named_scope(scopes.KV_CARRY):
                ck_all, cv_all = ck_all.at[i].set(ck), cv_all.at[i].set(cv)
            return (h, ck_all, cv_all), None
        h = carry
        lp, kind = xs
        h, _ = block(
            cfg, h, lp, kind, positions, attn_impl,
            mrope_positions=mrope_positions,
        )
        return h, None

    body = scan_body
    if remat == "full":
        body = jax.checkpoint(scan_body, prevent_cse=False)
    elif remat == "dots":
        body = jax.checkpoint(
            scan_body,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
            prevent_cse=False,
        )

    if kv_caches is not None:
        xs = (params["layers"], kinds, jnp.arange(cfg.n_layers))
        (h, ck, cv), _ = jax.lax.scan(body, (h,) + tuple(kv_caches), xs)
        new_caches = (ck, cv)
    else:
        h, new_caches = jax.lax.scan(body, h, (params["layers"], kinds))

    h = rms_norm(h, params["final_ln"])
    return h, new_caches


def decode(
    cfg: ModelConfig,
    params: Pytree,
    tokens: jax.Array,  # (B, 1) int32
    positions: jax.Array,  # (B,) each token's position
    kv_caches: Tuple[jax.Array, jax.Array],  # (L,B,Skv,Hkv,Dh) x2
) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One token per sequence against the stacked caches; returns (final
    hidden states (B,1,D), the caches with the token's k/v written).

    The caches are not carried through the layer scan, where the compiler
    copies each layer's slice out and back: each layer reads its slice where
    it is stored (:func:`attention_decode_at`), and the layers' new rows come
    out of the scan and are written after it (:func:`write_token_rows`), in
    place when the caches are donated.  One scatter of all the rows would
    make the compiler copy both caches into another layout around it.
    """
    B, S = tokens.shape
    if S != 1:
        raise ValueError(f"decode takes one token per sequence, got {S}")
    h = params["embed"][tokens].astype(cfg.dtype)

    def scan_body(h, xs):
        lp, kind, i = xs
        return decode_block(cfg, h, lp, kind, positions[:, None], kv_caches, i)

    xs = (params["layers"], cfg.layer_kinds(), jnp.arange(cfg.n_layers))
    h, rows = jax.lax.scan(scan_body, h, xs)  # (L, B, 1, Hkv, Dh) x2
    new_caches = write_token_rows(kv_caches, rows, positions)
    h = rms_norm(h, params["final_ln"])
    return h, new_caches


def write_token_rows(
    caches: Tuple[jax.Array, jax.Array],  # (L, B, Skv, Hkv, Dh) x2
    rows: Tuple[jax.Array, jax.Array],  # (L, B, 1, Hkv, Dh) x2
    positions: jax.Array,  # (B,)
) -> Tuple[jax.Array, jax.Array]:
    """Each slot's new k and v rows written at its position in every layer,
    in place when the caches are donated: on a TPU by the Pallas kernel
    (:mod:`repro.kernels.kv_write`), which rewrites the aligned window of
    positions that holds each row, in the caches' stored layout; elsewhere
    one ``dynamic_update_slice`` per slot and cache."""
    def reference():
        def write(b, caches):
            at = (0, b, positions[b], 0, 0)
            return tuple(
                jax.lax.dynamic_update_slice(c, jax.lax.dynamic_slice_in_dim(new, b, 1, 1), at)
                for c, new in zip(caches, rows)
            )

        # unrolled: for caches with a head size of 128 the TPU compiler
        # fails (an internal RET_CHECK) on a rolled loop here
        return jax.lax.fori_loop(0, positions.shape[0], write, tuple(caches), unroll=True)

    def kernel():
        from repro.kernels.kv_write import write_rows

        # positions are the minor dimension of the stored caches: this view is those bytes
        k, v = write_rows(*(jnp.moveaxis(c, 2, -1) for c in caches),
                          *(r[:, :, 0] for r in rows), positions)
        return jnp.moveaxis(k, -1, 2), jnp.moveaxis(v, -1, 2)

    with jax.named_scope(scopes.KV_WRITE):
        return jax.lax.platform_dependent(tpu=kernel, default=reference)


def lm_head(cfg: ModelConfig, params: Pytree, h: jax.Array) -> jax.Array:
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return h @ w.astype(h.dtype)


def lm_loss(
    cfg: ModelConfig,
    params: Pytree,
    h: jax.Array,  # (B, S, D) final hidden
    targets: jax.Array,  # (B, S) int32
    chunk: int = 512,
) -> jax.Array:
    """Chunked cross-entropy: the (B,S,V) logits are never materialized.

    This is the framework-level register-demotion move: the per-chunk
    running loss lives in the scan carry while logits stay chunk-sized.
    """
    B, S, D = h.shape
    n = -(-S // chunk)
    pad = n * chunk - S
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)), constant_values=-1)
    hc = h.reshape(B, n, chunk, D).transpose(1, 0, 2, 3)
    tc = targets.reshape(B, n, chunk).transpose(1, 0, 2)

    def step(acc, xs):
        hh, tt = xs
        logits = lm_head(cfg, params, hh).astype(jnp.float32)  # (B, c, V)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(tt, 0)[..., None], axis=-1
        )[..., 0]
        valid = tt >= 0
        nll = jnp.where(valid, logz - gold, 0.0)
        return (acc[0] + nll.sum(), acc[1] + valid.sum()), None

    # checkpoint the chunk step: without it, reverse-mode AD saves every
    # chunk's (B, c, V) logits — reassembling exactly the full-logits tensor
    # the chunking exists to avoid
    step = jax.checkpoint(step, prevent_cse=False)
    (total, count), _ = jax.lax.scan(step, (jnp.float32(0.0), jnp.int32(0)), (hc, tc))
    return total / jnp.maximum(count, 1)


# ---------------------------------------------------------------------------
# KV cache helpers
# ---------------------------------------------------------------------------


def init_kv_cache(
    cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16
) -> Tuple[jax.Array, jax.Array]:
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.dh)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def kv_cache_axes() -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
    ax = ("layers", "batch", "kv_seq", "heads", "head_dim")
    return ax, ax
