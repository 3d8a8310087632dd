"""The Zamba2 layer of ``models/hybrid.py`` against the plain reference.

``chipbench/reference_zamba2.py`` computes the published Zamba2 layer in
float32 with the recurrence stepped position by position; the program runs
the chunked SSD in prefill, the one-step update in decode, and reads the
KV caches in place.  At a small size on the CPU (9 layers with shared
blocks at 2, 4 and 7, 2 memory blocks used in turn, 2 groups of B and C,
adapter rank 4) both take the same seeded weights.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference_zamba2 import Dims, logits_at, make_weights
from repro.configs import get_config, reduced_config
from repro.models import Model, hybrid, mamba2
from repro.runtime.serving import Server

SMALL = reduced_config("zamba2_7b")


def _dims(cfg) -> Dims:
    return Dims(
        layers=cfg.n_layers, d_model=cfg.d_model, heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
        head_dim=cfg.dh, d_ff=cfg.d_ff, vocab=cfg.vocab, rope_theta=cfg.rope_theta,
        norm_eps=hybrid.NORM_EPS, hybrid_layer_ids=cfg.hybrid_layer_ids, mem_blocks=cfg.num_mem_blocks,
        adapter_rank=cfg.adapter_rank, ssm_heads=cfg.ssm_heads, ssm_head_dim=cfg.ssm_head_dim,
        ssm_state=cfg.ssm_state, ssm_groups=cfg.ssm_groups,
    )


def test_small_size_has_what_the_cell_has():
    assert SMALL.hybrid_layer_ids == (2, 4, 7) and SMALL.n_layers == 9
    assert (SMALL.num_mem_blocks, SMALL.ssm_groups, SMALL.adapter_rank) == (2, 2, 4)
    assert SMALL.dh == 2 * SMALL.d_model // SMALL.n_heads


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_make_weights_equals_model_init(dtype):
    cfg = dataclasses.replace(SMALL, dtype=dtype)
    key = jax.random.PRNGKey(11)
    params, _ = Model(cfg).init(key)
    weights = make_weights(_dims(cfg), key, dtype)
    assert jax.tree.structure(params) == jax.tree.structure(weights)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(weights)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("seed", [0, 7])
def test_forward_logits_match_reference(seed):
    """Both in float32: only the order of sums differs (the chunked dual
    form against the step-by-step recurrence, chunked softmax against the
    plain one), which moves logits of size ~0.5 by ~1e-6; 2e-5 is twenty
    times that, and any error of the layer's mathematics reads ~0.1."""
    cfg = dataclasses.replace(SMALL, dtype=jnp.float32)
    key = jax.random.PRNGKey(seed)
    model = Model(cfg)
    params, _ = model.init(key)
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 40), 1, cfg.vocab)
    h, _ = hybrid.forward(cfg, params, tokens)
    at = jnp.broadcast_to(jnp.arange(40)[None], (2, 40))
    want = logits_at(_dims(cfg), make_weights(_dims(cfg), key, jnp.float32), tokens, at)
    np.testing.assert_allclose(model.logits(params, h), want, atol=2e-5, rtol=0)


def test_served_decode_matches_reference_forward():
    """Rows prefilled to ragged lengths, one slot never filled, placed into
    one batch state as ``Server`` places them, then three decode steps
    through the cache; each step's logits against the reference's full
    forward over the row's own sequence.  Float32 weights and KV cache;
    the conv state is stored in bfloat16, whose rounding of the conv
    inputs moves these logits (size ~0.5) by up to 2.2e-3: 1e-2 allows
    that, and a state or cache written at the wrong place reads ~0.1."""
    cfg = dataclasses.replace(SMALL, dtype=jnp.float32)
    key = jax.random.PRNGKey(5)
    model = Model(cfg)
    params, _ = model.init(key)
    lengths, max_len = (5, 21, 0, 38), 48
    tokens = jax.random.randint(jax.random.PRNGKey(1), (len(lengths), max_len), 1, cfg.vocab)
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}, max_len)[1])
    state = None
    for slot, n in enumerate(lengths):
        if n == 0:
            continue
        one = prefill(params, tokens[slot:slot + 1, :n])
        if state is None:
            state = Server._tree_map_batch(
                lambda x, ax: jnp.zeros(x.shape[:ax] + (len(lengths),) + x.shape[ax + 1:],
                                        x.dtype), one)
        state = Server._insert_slot(state, one, jnp.int32(slot))
    decode = jax.jit(model.decode_step)
    rows, pos = jnp.arange(len(lengths)), jnp.array(lengths)
    got = []
    for step in range(3):
        h, state = decode(params, tokens[rows, pos + step][:, None], state)
        got.append(model.logits(params, h)[:, 0])
    assert [int(p) for p in state["pos"]] == [n + 3 for n in lengths]
    at = pos[:, None] + jnp.arange(3)[None]
    want = logits_at(_dims(cfg), make_weights(_dims(cfg), key, jnp.float32), tokens, at)
    np.testing.assert_allclose(jnp.stack(got, 1), want, atol=1e-2, rtol=0)


@pytest.mark.parametrize("groups", [1, 2])
def test_chunked_ssd_matches_the_one_step_update(groups):
    """The prefill's chunked dual form and decode's one-step update give
    the same outputs and final state, with B and C in groups (float32; the
    order of sums differs)."""
    B, S, H, P, N = 2, 40, 4, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(groups), 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    a = -jnp.linspace(1.0, 4.0, H)
    bm = jax.random.normal(ks[2], (B, S, groups, N))
    cm = jax.random.normal(ks[3], (B, S, groups, N))
    y, state = mamba2.ssd_chunked(x, dt, a, bm, cm, chunk=16)
    h = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        y_t, h = mamba2.ssd_decode_step(x[:, t], dt[:, t], a, bm[:, t], cm[:, t], h)
        ys.append(y_t)
    np.testing.assert_allclose(y, jnp.stack(ys, 1), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(state, h, atol=1e-4, rtol=1e-4)


def test_mamba2_370m_keeps_its_outputs():
    """One group, normalized over all of ``d_inner``: the reduced
    mamba2_370m's hidden states and decode step as they were before B and
    C took groups (float32; the values were read from that program)."""
    cfg = dataclasses.replace(reduced_config("mamba2_370m"), dtype=jnp.float32)
    model = Model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 1, cfg.vocab)
    h, _ = model._ssm_forward(params, tokens)
    np.testing.assert_allclose(h[:, ::13, :3], [
        [[0.8203107, 0.669788, 0.12710251], [0.30979165, -0.42522374, 0.25178492],
         [-0.43946412, -1.1692188, 2.4680312], [0.30521706, 0.5407844, 1.8419939]],
        [[0.17109536, 0.29494467, -0.29420507], [-1.4953482, -0.4209178, -0.5765998],
         [-1.2735366, 1.8195801, -0.22793612], [-0.05132032, -0.48997477, -0.10896852]],
    ], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(jnp.abs(h).sum()), 4119.5009765625, rtol=1e-5)
    _, state = model.prefill(params, {"tokens": tokens[:, :33]}, 40)
    h_dec, state = model.decode_step(params, tokens[:, 33:34], state)
    np.testing.assert_allclose(h_dec[:, 0, :4], [
        [1.2343245, 1.9343017, -0.23760311, -0.43356422],
        [1.1387268, -0.39563343, 0.62815815, 1.1352016],
    ], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(float(jnp.abs(state["ssm"]).sum()), 59.6767463684082, rtol=1e-5)


def test_published_config_has_the_catalog_widths():
    cfg = get_config("zamba2_7b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.dh, cfg.d_ff, cfg.vocab) == (
        81, 3584, 32, 224, 14336, 32000)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups) == (112, 64, 64, 2)
    assert cfg.hybrid_layer_ids == (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)
    assert (cfg.num_mem_blocks, cfg.adapter_rank) == (2, 128)
    assert hybrid.attention_scale(cfg) == pytest.approx(112 ** -0.5)
