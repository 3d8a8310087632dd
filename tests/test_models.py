"""Per-architecture smoke tests + model-level invariants.

Every assigned architecture instantiates its REDUCED config and runs one
forward/loss (and a prefill+decode round) on CPU, asserting output shapes
and finiteness, per the assignment.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, param_count, reduced_config, shape_cells
from repro.models import Model, hybrid, transformer
from repro.models.attention import attention_chunked, attention_xla
from repro.runtime.serving import Server


def _batch_for(cfg, B, S, key):
    k1, k2 = jax.random.split(key)
    batch = {
        "tokens": jax.random.randint(k1, (B, S), 1, cfg.vocab),
        "targets": jax.random.randint(k2, (B, S), 0, cfg.vocab),
    }
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(k1, (B, 8, cfg.d_model), jnp.bfloat16)
        batch["mrope_positions"] = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :, None], (B, S, 3)
        )
    if cfg.family == "audio":
        batch["frame_embeds"] = jax.random.normal(k1, (B, 16, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_train_step(arch):
    """Reduced config: one forward/loss; shapes + no NaNs (the deliverable)."""
    cfg = reduced_config(arch)
    model = Model(cfg, attn_impl="xla")
    params, axes = model.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, 2, 32, jax.random.PRNGKey(1))
    loss, grads = jax.value_and_grad(model.train_loss)(params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss))
    # init loss must be near ln(vocab) (healthy initialization)
    assert abs(float(loss) - math.log(cfg.vocab)) < 1.5
    for leaf in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32)))), arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_arch_smoke_prefill_decode(arch):
    cfg = reduced_config(arch)
    model = Model(cfg, attn_impl="xla")
    params, _ = model.init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, 2, 8, jax.random.PRNGKey(1))
    batch.pop("targets")
    h, state = model.prefill(params, batch, max_len=16)
    if cfg.family == "audio":
        # enc-dec prefill returns the encoder output; decoding starts at BOS
        assert h.shape == (2, batch["frame_embeds"].shape[1], cfg.d_model)
    else:
        assert h.shape[:2] == (2, 8)
    tok = jnp.argmax(model.logits(params, h[:, -1:]), -1).astype(jnp.int32)
    h2, state2 = model.decode_step(params, tok, state)
    assert h2.shape == (2, 1, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(h2.astype(jnp.float32))))
    assert int(state2["pos"][0]) == int(state["pos"][0]) + 1


@pytest.mark.parametrize("arch", ["stablelm_3b", "gemma3_1b", "mamba2_370m", "zamba2_2_7b",
                                  "zamba2_7b"])
def test_decode_consistency_with_forward(arch):
    """KV-cache / SSM-state decode must match the full forward (fp32, with
    fp32 caches isolated from quantization by tolerance)."""
    cfg = dataclasses.replace(reduced_config(arch), dtype=jnp.float32)
    model = Model(cfg, attn_impl="xla")
    params, _ = model.init(jax.random.PRNGKey(0))
    B, S = 2, 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 1, cfg.vocab)
    if cfg.family in ("dense", "moe", "vlm"):
        h_full, _ = transformer.forward(cfg, params, toks, attn_impl="xla")
    elif cfg.family == "ssm":
        h_full, _ = model._ssm_forward(params, toks)
    else:
        h_full, _ = hybrid.forward(cfg, params, toks, attn_impl="xla")
    _, state = model.prefill(params, {"tokens": toks[:, :S]}, max_len=S + 4)
    h_dec, _ = model.decode_step(params, toks[:, S : S + 1], state)
    err = float(jnp.abs(h_dec[:, 0] - h_full[:, S]).max())
    assert err < 5e-2, err  # bf16 cache quantization bound


@pytest.mark.parametrize("lengths", [(5, 21, 32, 38), (0, 21)], ids=["ragged", "empty_slot"])
@pytest.mark.parametrize(
    "arch",
    ["stablelm_3b", "qwen2_7b", "gemma3_1b", "llama4_scout_17b_a16e", "zamba2_2_7b", "zamba2_7b"],
)
def test_decode_at_ragged_positions_matches_forward(arch, lengths):
    """Rows prefilled to different lengths and placed into one batch state,
    as ``Server`` places them, each decode one token that matches the full
    forward over the row's own sequence.  The lengths cross gemma3's window
    (16), llama4's attention chunk (32) and the hybrids' SSD chunk (16); a
    slot never filled sits at position 0 and attends to its token alone,
    from zero SSM and conv states."""
    cfg = dataclasses.replace(reduced_config(arch), dtype=jnp.float32)
    model = Model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    max_len = 40
    toks = jax.random.randint(jax.random.PRNGKey(1), (len(lengths), max_len), 1, cfg.vocab)
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, max_len)[1])
    state = None
    for slot, n in enumerate(lengths):
        if n == 0:
            continue
        one = prefill(params, toks[slot:slot + 1, :n])
        if state is None:
            state = Server._tree_map_batch(
                lambda x, ax: jnp.zeros(
                    x.shape[:ax] + (len(lengths),) + x.shape[ax + 1:], x.dtype
                ),
                one,
            )
        state = Server._insert_slot(state, one, jnp.int32(slot))
    # two steps, so the second reads the rows the first wrote
    decode = jax.jit(model.decode_step)
    rows, pos = jnp.arange(len(lengths)), jnp.array(lengths)
    h_dec = []
    for step in range(2):
        h, state = decode(params, toks[rows, pos + step][:, None], state)
        assert bool(jnp.all(jnp.isfinite(h)))
        h_dec.append(h[:, 0])
    assert [int(p) for p in state["pos"]] == [n + 2 for n in lengths]
    forward = hybrid.forward if cfg.family == "hybrid" else transformer.forward
    for slot, n in enumerate(lengths):
        h_full, _ = forward(cfg, params, toks[slot:slot + 1, :n + 2], attn_impl="xla")
        for step in range(2):
            err = float(jnp.abs(h_dec[step][slot] - h_full[0, n + step]).max())
            assert err < 5e-2, (slot, n, step, err)  # bf16 cache quantization bound


def test_full_configs_match_assignment():
    """The full (non-reduced) configs carry the exact assigned dimensions."""
    spec = {
        "stablelm_3b": (32, 2560, 32, 32, 6912, 50304),
        "gemma3_1b": (26, 1152, 4, 1, 6912, 262144),
        "qwen2_7b": (28, 3584, 28, 4, 18944, 152064),
        "granite_8b": (36, 4096, 32, 8, 14336, 49152),
        "qwen2_moe_a2_7b": (24, 2048, 16, 16, 1408, 151936),
        "llama4_scout_17b_a16e": (48, 5120, 40, 8, 8192, 202048),
        "qwen2_vl_2b": (28, 1536, 12, 2, 8960, 151936),
        "whisper_large_v3": (32, 1280, 20, 20, 5120, 51866),
        "mamba2_370m": (48, 1024, 0, 0, 0, 50280),
        "zamba2_2_7b": (54, 2560, 32, 32, 10240, 32000),
        "zamba2_7b": (81, 3584, 32, 32, 14336, 32000),
    }
    for arch, (L, D, Hq, Hkv, F, V) in spec.items():
        cfg = get_config(arch)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab) == (
            L, D, Hq, Hkv, F, V,
        ), arch
    assert get_config("qwen2_moe_a2_7b").moe.n_experts == 60
    assert get_config("qwen2_moe_a2_7b").moe.top_k == 4
    assert get_config("llama4_scout_17b_a16e").moe.n_experts == 16
    assert get_config("llama4_scout_17b_a16e").moe.top_k == 1
    assert get_config("mamba2_370m").ssm_state == 128
    assert get_config("zamba2_2_7b").ssm_state == 64
    assert get_config("zamba2_2_7b").hybrid_layer_ids == (6, 12, 18, 24, 30, 36, 42, 48)
    assert get_config("zamba2_2_7b").dh == 2 * 2560 // 32
    assert get_config("zamba2_7b").ssm_groups == 2


def test_shape_cells_cover_assignment():
    total = skipped = 0
    for arch in ARCH_IDS:
        cells = shape_cells(arch)
        assert [c.name for c in cells] == ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
        total += len(cells)
        skipped += sum(c.skipped for c in cells)
        # long_500k runs exactly for the sub-quadratic archs
        long = cells[-1]
        if arch in ("gemma3_1b", "llama4_scout_17b_a16e", "mamba2_370m", "zamba2_2_7b"):
            assert not long.skipped, arch
        else:
            assert long.skipped, arch
    assert total == 40
    assert skipped == 6


def test_param_counts_plausible():
    """Full configs land near their nameplate sizes."""
    expects = {
        "qwen2_7b": (6.5e9, 8.5e9),
        "granite_8b": (7e9, 9e9),
        "mamba2_370m": (3e8, 5e8),
        "gemma3_1b": (0.8e9, 1.6e9),
        "llama4_scout_17b_a16e": (90e9, 130e9),  # total (not active) params
    }
    for arch, (lo, hi) in expects.items():
        n = param_count(get_config(arch))
        assert lo <= n <= hi, (arch, n)


def test_attention_chunked_matches_xla():
    key = jax.random.PRNGKey(0)
    B, S, Hq, Hkv, Dh = 2, 96, 4, 2, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, Dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    from repro.models.common import causal_mask_bias

    for window in (None, 17):
        want = attention_xla(q, k, v, bias=causal_mask_bias(pos, pos, window=window))
        got = attention_chunked(q, k, v, pos, pos, window=window, kv_chunk=32)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_moe_capacity_matches_dense_when_no_drop():
    cfg = reduced_config("qwen2_moe_a2_7b")
    m = dataclasses.replace(cfg.moe, capacity_factor=8.0)
    model = Model(dataclasses.replace(cfg, moe=m, dtype=jnp.float32), attn_impl="xla")
    params, _ = model.init(jax.random.PRNGKey(0))
    lp = jax.tree.map(lambda w: w[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(3), (300, cfg.d_model))
    y_cap = transformer.moe_ffn(x, lp, m, dense_path_max_tokens=0)
    y_dense = transformer.moe_ffn(x, lp, m, dense_path_max_tokens=1024)
    np.testing.assert_allclose(y_cap, y_dense, atol=1e-5, rtol=1e-5)


def test_mrope_differs_from_rope_only_in_rotation():
    from repro.models.common import apply_mrope, apply_rope

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 32))
    pos = jnp.broadcast_to(jnp.arange(16, dtype=jnp.int32)[None], (2, 16))
    mpos = jnp.stack([pos, pos, pos], axis=-1)
    # with identical position streams, M-RoPE == RoPE at the same theta
    a = apply_rope(x, pos, theta=1e6)
    b = apply_mrope(x, mpos, theta=1e6)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
