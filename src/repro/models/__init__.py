"""Unified model API over all assigned architecture families.

``Model`` dispatches on ``ModelConfig.family``:

* ``dense`` / ``moe`` / ``vlm``  -> :mod:`repro.models.transformer`
* ``ssm``                        -> pure Mamba2 stack (transformer-free)
* ``hybrid``                     -> :mod:`repro.models.hybrid` (Zamba2)
* ``audio``                      -> :mod:`repro.models.encdec` (Whisper)

Every family exposes the same four entry points used by the trainer and
the server:

    init(rng)                          -> (params, logical_axes)
    train_loss(params, batch)          -> scalar loss
    prefill(params, batch)             -> (hidden, cache_state)
    decode_step(params, batch, state)  -> (hidden, new_state)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import encdec, hybrid, mamba2, scopes, transformer
from .transformer import BIG, ModelConfig, MoEConfig

Pytree = Any

__all__ = ["Model", "ModelConfig", "MoEConfig", "BIG"]


class Model:
    def __init__(self, cfg: ModelConfig, attn_impl: str = "chunked", remat: str = "none"):
        self.cfg = cfg
        self.attn_impl = attn_impl
        self.remat = remat

    # -- init -----------------------------------------------------------------

    def abstract_init(self) -> Tuple[Pytree, Pytree]:
        """(ShapeDtypeStruct params, logical axes) without allocating anything:
        what stands in for multi-billion-parameter weights when compiling or
        counting them."""
        box: Dict[str, Any] = {}

        def capture(k):
            p, a = self.init(k)
            box["axes"] = a
            return p

        params_struct = jax.eval_shape(capture, jax.random.PRNGKey(0))
        return params_struct, box["axes"]

    def init(self, rng: jax.Array) -> Tuple[Pytree, Pytree]:
        cfg = self.cfg
        if cfg.family in ("dense", "moe", "vlm"):
            return transformer.init_params(cfg, rng)
        if cfg.family == "hybrid":
            return hybrid.init_params(cfg, rng)
        if cfg.family == "ssm":
            return self._init_ssm(rng)
        if cfg.family == "audio":
            return encdec.init_params(cfg, rng)
        raise ValueError(cfg.family)

    def _init_ssm(self, rng):
        cfg = self.cfg
        ks = jax.random.split(rng, 3)

        def init_one(k):
            p, _ = mamba2.init_mamba_layer(
                k, cfg.d_model, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                dtype=cfg.dtype, groups=cfg.ssm_groups,
            )
            return p

        _, m_axes = mamba2.init_mamba_layer(
            ks[0], cfg.d_model, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
            dtype=cfg.dtype, groups=cfg.ssm_groups,
        )
        layers = jax.vmap(init_one)(jax.random.split(ks[1], cfg.n_layers))
        params = {
            "embed": jnp.zeros((cfg.vocab, cfg.d_model), cfg.dtype)
            + 0.02 * jax.random.normal(ks[2], (cfg.vocab, cfg.d_model), cfg.dtype),
            "mamba": layers,
            "final_ln": jnp.zeros((cfg.d_model,), cfg.dtype),
        }
        axes = {
            "embed": ("vocab", "embed_tbl"),
            "mamba": {k: ("layers",) + v for k, v in m_axes.items()},
            "final_ln": ("embed",),
        }
        return params, axes

    # -- forward paths ----------------------------------------------------------

    def _ssm_forward(self, params, tokens, ssm_states=None, conv_states=None,
                     positions=None, decode=False):
        cfg = self.cfg
        B, S = tokens.shape
        h = params["embed"][tokens].astype(cfg.dtype)
        L = cfg.n_layers
        if ssm_states is None:
            d_inner, conv_dim = mamba2.mamba_dims(
                cfg.d_model, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
            )
            ssm_states = jnp.zeros(
                (L, B, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32
            )
            conv_states = jnp.zeros((L, B, mamba2.D_CONV - 1, conv_dim), jnp.bfloat16)

        def body(carry, xs):
            hh = carry
            lp, ssm_i, conv_i = xs
            hh, new_ssm, new_conv = mamba2.mamba_layer(
                lp, hh, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                chunk=cfg.ssm_chunk,
                ssm_state=ssm_i if decode else None,
                conv_state=conv_i if decode else None,
                decode=decode, groups=cfg.ssm_groups,
            )
            return hh, (new_ssm, new_conv)

        fn = body
        if self.remat in ("dots", "full"):
            fn = jax.checkpoint(body, prevent_cse=False)
        h, (nssm, nconv) = jax.lax.scan(fn, h, (params["mamba"], ssm_states, conv_states))
        h = transformer.rms_norm(h, params["final_ln"])
        return h, {"ssm": nssm, "conv": nconv}

    # -- public API ---------------------------------------------------------------

    def train_loss(self, params: Pytree, batch: Dict[str, jax.Array]) -> jax.Array:
        cfg = self.cfg
        tokens, targets = batch["tokens"], batch["targets"]
        if cfg.family in ("dense", "moe", "vlm"):
            h, _ = transformer.forward(
                cfg, params, tokens,
                attn_impl=self.attn_impl, remat=self.remat,
                patch_embeds=batch.get("patch_embeds"),
                mrope_positions=batch.get("mrope_positions"),
            )
            return transformer.lm_loss(cfg, params, h, targets)
        if cfg.family == "hybrid":
            h, _ = hybrid.forward(
                cfg, params, tokens, attn_impl=self.attn_impl, remat=self.remat
            )
            return hybrid.lm_head_loss(cfg, params, h, targets)
        if cfg.family == "ssm":
            h, _ = self._ssm_forward(params, tokens)
            tied = dataclasses.replace(cfg, tie_embeddings=True)
            return transformer.lm_loss(tied, {"embed": params["embed"]}, h, targets)
        if cfg.family == "audio":
            enc = encdec.encode(cfg, params, batch["frame_embeds"], self.attn_impl)
            h = encdec.decode_train(cfg, params, enc, tokens, self.attn_impl, self.remat)
            tied = dataclasses.replace(cfg, tie_embeddings=True)
            return transformer.lm_loss(tied, {"embed": params["embed"]}, h, targets)
        raise ValueError(cfg.family)

    def prefill(self, params: Pytree, batch: Dict[str, jax.Array], max_len: int):
        """Processes the prompt; returns (hidden, decode state)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        if cfg.family in ("dense", "moe", "vlm"):
            caches = transformer.init_kv_cache(cfg, B, max_len)
            cache_pos = jnp.broadcast_to(
                jnp.arange(max_len, dtype=jnp.int32)[None], (B, max_len)
            )
            h, new_caches = transformer.forward(
                cfg, params, tokens,
                attn_impl=self.attn_impl,
                patch_embeds=batch.get("patch_embeds"),
                mrope_positions=batch.get("mrope_positions"),
                kv_caches=caches, cache_positions=cache_pos,
            )
            return h, {"kv": new_caches, "pos": jnp.full((B,), S, jnp.int32)}
        if cfg.family == "ssm":
            h, st = self._ssm_forward(params, tokens)
            st["pos"] = jnp.full((B,), S, jnp.int32)
            return h, st
        if cfg.family == "hybrid":
            h, st = hybrid.forward(cfg, params, tokens, attn_impl=self.attn_impl,
                                   max_len=max_len)
            st["pos"] = jnp.full((B,), S, jnp.int32)
            return h, st
        if cfg.family == "audio":
            enc = encdec.encode(cfg, params, batch["frame_embeds"], self.attn_impl)
            kv = (
                jnp.zeros((cfg.n_layers, B, max_len, cfg.n_kv_heads, cfg.dh), cfg.dtype),
                jnp.zeros((cfg.n_layers, B, max_len, cfg.n_kv_heads, cfg.dh), cfg.dtype),
            )
            return enc, {"kv": kv, "enc": enc, "pos": jnp.zeros((B,), jnp.int32)}
        raise ValueError(cfg.family)

    def decode_step(self, params: Pytree, tokens: jax.Array, state: Dict[str, Any]):
        """One new token per sequence against the cached state.

        For the ``dense``/``moe``/``vlm`` and ``hybrid`` families the token
        shape chooses the attention, not ``attn_impl``:
        :func:`transformer.decode` and :func:`hybrid.decode` read each cache
        in place and write only the new token's rows."""
        cfg = self.cfg
        B = tokens.shape[0]
        positions = state["pos"][:, None]
        if cfg.family in ("dense", "moe", "vlm"):
            h, new_kv = transformer.decode(cfg, params, tokens, state["pos"], state["kv"])
            return h, {"kv": new_kv, "pos": state["pos"] + 1}
        if cfg.family == "ssm":
            h, st = self._ssm_forward(
                params, tokens, ssm_states=state["ssm"], conv_states=state["conv"],
                decode=True,
            )
            st["pos"] = state["pos"] + 1
            return h, st
        if cfg.family == "hybrid":
            h, st = hybrid.decode(cfg, params, tokens, state["pos"], state)
            st["pos"] = state["pos"] + 1
            return h, st
        if cfg.family == "audio":
            kv = state["kv"]
            max_len = kv[0].shape[2]
            cache_pos = jnp.broadcast_to(
                jnp.arange(max_len, dtype=jnp.int32)[None], (B, max_len)
            )
            h, new_kv = encdec.decode_step(
                cfg, params, state["enc"], tokens, positions, kv, cache_pos,
                self.attn_impl,
            )
            return h, {"kv": new_kv, "enc": state["enc"], "pos": state["pos"] + 1}
        raise ValueError(cfg.family)

    def decode_kv_traffic(self, positions, state: Pytree) -> Optional[Tuple[int, int, int]]:
        """(blocks copied, blocks stored, bytes rewritten) when a decode step
        of slots at ``positions`` goes through the batch ``state``'s caches,
        ``(N, B, S, Hkv, Dh)`` under ``kv``, counted on the host: the blocks of
        each cache entry (a layer's, or an application's of a shared block)
        that the decode-attention kernel reads for full attention, and the
        bytes of the tiles that the row-write kernel rewrites, K and V
        together; None for a family whose decode step does not use them."""
        if self.cfg.family not in ("dense", "moe", "vlm", "hybrid"):
            return None
        from repro.kernels import decode_attention, kv_write

        cache = state["kv"][0]
        _, _, max_len, n_kv_heads, dh = cache.shape
        _, block = decode_attention.block_sizes(n_kv_heads, dh, max_len, cache.dtype.itemsize)
        written = sum(kv_write.write_bytes(c.shape, c.dtype.itemsize) for c in state["kv"])
        return (*decode_attention.read_share(positions, max_len, block), written)

    def logits(self, params: Pytree, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        with jax.named_scope(scopes.LM_HEAD):
            if cfg.family in ("dense", "moe", "vlm") and not cfg.tie_embeddings:
                return transformer.lm_head(cfg, params, h)
            return h @ params["embed"].T.astype(h.dtype)
