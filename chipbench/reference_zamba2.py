"""Plain reference for the Zamba2 configurations the benchmark serves.

It imports nothing of the program and takes nothing the program made.  The
weights are made anew from the seed by the initialization the program
documents (``models/hybrid.py:init_params``, ``models/mamba2.py``): the key
is split into the embedding's, the Mamba2 layers' (one each), the shared
blocks' (one each, split again per matrix) and the applications' (likewise);
truncated normals at ±2 std, ``1/sqrt(d_in)`` for every projection, 0.2 for
the conv, 0.02 for the embedding; zero norm scales and biases;
``A = -linspace(1, 16, H)``, ``D = 1``, ``dt_bias = 0``.  They are kept in the
type they are served in (bfloat16, the SSM's own parameters float32), and
everything is computed in float32 at ``HIGHEST`` matmul precision.

The layer, for ``i = 0 .. L-1``, with ``e`` the embedding and ``h`` the
residual stream (Hugging Face's ``modeling_zamba2.py``):

* where ``i`` is the ``j``-th of ``hybrid_layer_ids``, shared block
  ``b = j mod num_mem_blocks`` computes ``t`` from ``x = RMSNorm_b([h ; e])``:
  rotary q and k over the whole head, causal softmax at ``(head_dim/2)^-0.5``,
  ``o = attn Wo_b``; ``u = RMSNorm_b,ff(o)``,
  ``g, up = split(u W_gu_b + (u A_j) B_j)``, ``t = ((gelu(g) up) W_down_b) Lin_j``
  (erf GELU); elsewhere ``t = 0``;
* ``h <- h + Mamba2_i(RMSNorm_i(h + t))``: ``[z, xBC, dt] = x W_in``, a causal
  depthwise conv of width 4 with bias and SiLU over ``xBC``, then ``x, B, C``
  (B and C in groups; head ``n`` reads group ``n // (H / G)``),
  ``dt = softplus(dt + dt_bias)``, and the recurrence, step by step over the
  positions: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
  ``y_t = S_t C_t + D x_t``; then ``RMSNorm(y * silu(z))`` over each group's
  channels, ``W_out``;
* a final RMSNorm, and logits against the tied embedding.

RMSNorm is the program's, with the unit offset, at the file's ``rms_norm_eps``:
``x * rsqrt(mean(x^2) + eps) * (1 + scale)``.

``precision="fp8"`` is the control: the same computation with every weight
and every matmul input rounded to float8 e4m3 (weights scaled per output
column, activations per row).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_E4M3_MAX = 448.0
_CONV = 4


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    hybrid_layer_ids: Tuple[int, ...]
    mem_blocks: int
    adapter_rank: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int

    @classmethod
    def from_config(cls, model: Dict) -> "Dims":
        """``model`` holds the configuration file's keys (Hugging Face's
        Zamba2 names)."""
        d = int(model["hidden_size"])
        dims = cls(
            layers=int(model["num_hidden_layers"]),
            d_model=d,
            heads=int(model["num_attention_heads"]),
            kv_heads=int(model["num_key_value_heads"]),
            head_dim=int(model["attention_head_dim"]),
            d_ff=int(model["ffn_hidden_size"]),
            vocab=int(model["vocab_size"]),
            rope_theta=float(model["rope_theta"]),
            norm_eps=float(model["rms_norm_eps"]),
            hybrid_layer_ids=tuple(int(i) for i in model["hybrid_layer_ids"]),
            mem_blocks=int(model["num_mem_blocks"]),
            adapter_rank=int(model["adapter_rank"]) if model["use_shared_mlp_adapter"] else 0,
            ssm_heads=int(model["n_mamba_heads"]),
            ssm_head_dim=int(model["mamba_headdim"]),
            ssm_state=int(model["mamba_d_state"]),
            ssm_groups=int(model["mamba_ngroups"]),
        )
        kinds = ["hybrid" if i in dims.hybrid_layer_ids else "mamba" for i in range(dims.layers)]
        checks = {
            "layers_block_type": list(model["layers_block_type"]) == kinds,
            "attention_hidden_size": int(model["attention_hidden_size"]) == 2 * d,
            "mamba_expand": int(model["mamba_expand"]) * d == dims.d_inner,
            "mamba_d_conv": int(model["mamba_d_conv"]) == _CONV,
            "hidden_act": model["hidden_act"] == "gelu",
            "use_shared_attention_adapter": not model["use_shared_attention_adapter"],
            "use_conv_bias": bool(model["use_conv_bias"]),
            "add_bias_linear": not model["add_bias_linear"],
            "use_mem_rope": bool(model["use_mem_rope"]),
        }
        wrong = [k for k, ok in checks.items() if not ok]
        if wrong:
            raise ValueError(f"the reference does not compute this Zamba2 layer: {wrong}")
        return dims

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state


def make_weights(m: Dims, key: jax.Array, dtype=jnp.bfloat16) -> Dict:
    """The served weights for ``key``; layers, blocks and applications each
    stacked on axis 0."""
    k_embed, k_mamba, k_blocks, k_apps = jax.random.split(key, 4)

    def normal(k, shape, std):
        x = jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * std
        return x.astype(dtype)

    def dense(k, d_in, d_out):
        return normal(k, (d_in, d_out), 1.0 / math.sqrt(d_in))

    d, h = m.d_model, m.ssm_heads
    proj = 2 * m.d_inner + 2 * m.ssm_groups * m.ssm_state + h

    def mamba(k):
        ks = jax.random.split(k, 4)
        return {
            "in_proj": dense(ks[0], d, proj),
            "conv_w": normal(ks[1], (_CONV, m.conv_dim), 0.2),
            "conv_b": jnp.zeros((m.conv_dim,), dtype),
            "a_log": jnp.log(jnp.linspace(1.0, 16.0, h)).astype(jnp.float32),
            "d_skip": jnp.ones((h,), jnp.float32),
            "dt_bias": jnp.zeros((h,), jnp.float32),
            "norm": jnp.zeros((m.d_inner,), dtype),
            "out_proj": dense(ks[2], m.d_inner, d),
            "ln": jnp.zeros((d,), dtype),
        }

    def block(k):
        ks = jax.random.split(k, 6)
        q_width, kv_width = m.heads * m.head_dim, m.kv_heads * m.head_dim
        return {
            "ln_attn": jnp.zeros((2 * d,), dtype),
            "wq": dense(ks[0], 2 * d, q_width),
            "wk": dense(ks[1], 2 * d, kv_width),
            "wv": dense(ks[2], 2 * d, kv_width),
            "wo": dense(ks[3], q_width, d),
            "ln_ff": jnp.zeros((d,), dtype),
            "w_gate_up": dense(ks[4], d, 2 * m.d_ff),
            "w_down": dense(ks[5], m.d_ff, d),
        }

    def app(k):
        ks = jax.random.split(k, 3)
        out = {"linear": dense(ks[2], d, d)}
        if m.adapter_rank:
            out["adapter_in"] = dense(ks[0], d, m.adapter_rank)
            out["adapter_out"] = dense(ks[1], m.adapter_rank, 2 * m.d_ff)
        return out

    return {
        "embed": normal(k_embed, (m.vocab, d), 0.02),
        "mamba": jax.vmap(mamba)(jax.random.split(k_mamba, m.layers)),
        "blocks": jax.vmap(block)(jax.random.split(k_blocks, m.mem_blocks)),
        "apps": jax.vmap(app)(jax.random.split(k_apps, len(m.hybrid_layer_ids))),
        "final_ln": jnp.zeros((d,), dtype),
    }


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round ``x`` to float8 e4m3, scaled so each slice along ``axis`` spans it."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _matmul(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _fp8(x, -1), _fp8(w, 0)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, Dh); rotate-half pairing over the whole head."""
    dh = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = positions[:, :, None].astype(jnp.float32) * inv_freq
    sin, cos = jnp.sin(angles)[:, :, None, :], jnp.cos(angles)[:, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _shared(m: Dims, precision: str, bw, aw, h, e) -> jax.Array:
    """``t`` of one application: block weights ``bw``, application's ``aw``."""
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    x = _rms(jnp.concatenate([h, e], axis=-1), bw["ln_attn"], m.norm_eps)
    q = _matmul(x, bw["wq"], precision).reshape(b, s, m.heads, m.head_dim)
    k = _matmul(x, bw["wk"], precision).reshape(b, s, m.kv_heads, m.head_dim)
    v = _matmul(x, bw["wv"], precision).reshape(b, s, m.kv_heads, m.head_dim)
    q, k = _rope(q, positions, m.rope_theta), _rope(k, positions, m.rope_theta)
    group = m.heads // m.kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / math.sqrt(m.head_dim / 2)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST).reshape(b, s, -1)
    u = _rms(_matmul(o, bw["wo"], precision), bw["ln_ff"], m.norm_eps)
    gu = _matmul(u, bw["w_gate_up"], precision)
    if m.adapter_rank:
        gu = gu + _matmul(_matmul(u, aw["adapter_in"], precision), aw["adapter_out"], precision)
    g, up = jnp.split(gu, 2, axis=-1)
    return _matmul(_matmul(jax.nn.gelu(g, approximate=False) * up, bw["w_down"], precision),
                   aw["linear"], precision)


def _recurrence(x, dt, a, bm, cm, groups: int):
    """Step by step over positions.  x (B,S,H,P), dt (B,S,H), a (H,),
    bm/cm (B,S,G,N) -> y (B,S,H,P) without the skip."""
    heads_per_group = x.shape[2] // groups
    bh = jnp.repeat(bm, heads_per_group, axis=2)  # (B, S, H, N): head n's group
    ch = jnp.repeat(cm, heads_per_group, axis=2)

    def step(state, xs):  # state (B, H, P, N)
        x_t, dt_t, b_t, c_t = xs
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    b, _, h, p = x.shape
    init = jnp.zeros((b, h, p, bm.shape[-1]), jnp.float32)
    seq = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, init, (seq(x), seq(dt), seq(bh), seq(ch)), unroll=4)
    return jnp.moveaxis(y, 0, 1)


def _mamba(m: Dims, precision: str, h, t, lw) -> jax.Array:
    b, s, _ = h.shape
    g, n, heads = m.ssm_groups, m.ssm_state, m.ssm_heads
    x = _rms(h + t, lw["ln"], m.norm_eps)
    z, xbc, dt = jnp.split(_matmul(x, lw["in_proj"], precision),
                           [m.d_inner, m.d_inner + m.conv_dim], axis=-1)
    w = lw["conv_w"].astype(jnp.float32)
    padded = jnp.pad(xbc, ((0, 0), (_CONV - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + s] * w[i] for i in range(_CONV)) + lw["conv_b"].astype(jnp.float32)
    xs, bm, cm = jnp.split(jax.nn.silu(conv), [m.d_inner, m.d_inner + g * n], axis=-1)
    xs = xs.reshape(b, s, heads, m.ssm_head_dim)
    dt = jax.nn.softplus(dt + lw["dt_bias"])
    y = _recurrence(xs, dt, -jnp.exp(lw["a_log"]), bm.reshape(b, s, g, n),
                    cm.reshape(b, s, g, n), g)
    y = (y + lw["d_skip"][:, None] * xs).reshape(b, s, m.d_inner) * jax.nn.silu(z)
    y = _rms(y.reshape(b, s, g, -1), lw["norm"].reshape(g, -1), m.norm_eps)
    return h + _matmul(y.reshape(b, s, m.d_inner), lw["out_proj"], precision)


def logits_at(
    m: Dims, w: Dict, tokens: jax.Array, at: jax.Array, precision: str = "f32"
) -> jax.Array:
    """Logits (B, n, V) in float32 at positions ``at`` (B, n) of ``tokens`` (B, S).

    Every layer is causal, so padding after a row's last read position
    changes nothing that is read.
    """
    with jax.default_matmul_precision("highest"):
        e = w["embed"][tokens].astype(jnp.float32)
        h = e
        ids = list(m.hybrid_layer_ids)
        bounds = sorted(set([0] + ids + [m.layers]))
        for lo, hi in zip(bounds, bounds[1:]):
            t = jnp.zeros_like(h)
            if lo in ids:
                j = ids.index(lo)
                pick = lambda tree, n: jax.tree.map(lambda x: x[n], tree)
                t = _shared(m, precision, pick(w["blocks"], j % m.mem_blocks), pick(w["apps"], j),
                            h, e)
            layers = jax.tree.map(lambda x: x[lo:hi], w["mamba"])

            def layer(carry, lw):
                h, t = carry
                return (_mamba(m, precision, h, t, lw), jnp.zeros_like(t)), None

            (h, _), _ = jax.lax.scan(layer, (h, t), layers)
        h = jnp.take_along_axis(h, at[:, :, None], axis=1)
        h = _rms(h, w["final_ln"], m.norm_eps)
        return _matmul(h, w["embed"].T, precision)


@functools.partial(jax.jit, static_argnums=(0,))
def served_gaps(m: Dims, w, tokens, at, emitted):
    """As :func:`chipbench.serve_check.served_gaps`, against this reference."""
    logits = logits_at(m, w, tokens, at)
    got = jnp.take_along_axis(logits, emitted[..., None], axis=-1)[..., 0]
    return logits.max(-1) - got, jnp.isfinite(logits).all()


@functools.partial(jax.jit, static_argnums=(0,))
def control_gaps(m: Dims, w, tokens, at, emitted):
    """As :func:`chipbench.serve_check.control_gaps`, against this reference."""
    del emitted  # the control's own first choice is read instead
    ref = logits_at(m, w, tokens, at)
    first = jnp.argmax(logits_at(m, w, tokens, at, "fp8"), axis=-1)
    got = jnp.take_along_axis(ref, first[..., None], axis=-1)[..., 0]
    return ref.max(-1) - got, jnp.isfinite(ref).all()
