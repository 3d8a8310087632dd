"""Trainer / checkpoint / serving / fault-tolerance integration tests."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, restore_tree, save_tree
from repro.configs import reduced_config
from repro.data import DataConfig, SyntheticLM
from repro.launch.mesh import make_host_mesh
from repro.models import Model
from repro.optim import AdamWConfig
from repro.runtime import ServeConfig, Server, TrainConfig, Trainer
from repro.runtime.serving import Request
from repro.runtime.trainer import StragglerDetector


def _mk_trainer(tmp_path, steps=6, ckpt_every=3, arch="stablelm_3b", **tkw):
    cfg = reduced_config(arch)
    mesh = make_host_mesh()
    tcfg = TrainConfig(
        steps=steps,
        checkpoint_every=ckpt_every,
        checkpoint_dir=str(tmp_path / "ckpt"),
        attn_impl="xla",
        **tkw,
    )
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4, seed=7)
    return Trainer(cfg, AdamWConfig(lr=1e-3, warmup_steps=2, total_steps=steps), tcfg, dcfg, mesh)


def test_training_loss_decreases(tmp_path):
    tr = _mk_trainer(tmp_path, steps=30, ckpt_every=100)
    out = tr.run()
    losses = out["losses"]
    assert len(losses) == 30
    first, last = np.mean(losses[:5]), np.mean(losses[-5:])
    assert last < first - 0.2, (first, last)


def test_checkpoint_restart_is_bit_exact(tmp_path):
    # uninterrupted run
    tr1 = _mk_trainer(tmp_path / "a", steps=8, ckpt_every=4)
    out1 = tr1.run()

    # interrupted run: dies once at step 5, restarts from step-4 checkpoint
    boom = {"armed": True}

    def injector(step):
        if step == 5 and boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected node failure")

    tr2 = _mk_trainer(tmp_path / "b", steps=8, ckpt_every=4)
    out2 = tr2.run(fault_injector=injector)
    assert out2["restarts"] == 1
    # deterministic data replay => the final losses agree exactly
    np.testing.assert_allclose(out1["losses"][-1], out2["losses"][-1], rtol=1e-6)
    leaves1 = jax.tree.leaves(out1["params"])
    leaves2 = jax.tree.leaves(out2["params"])
    for a, b in zip(leaves1, leaves2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomicity_and_keep_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    tree = {"w": jnp.arange(8.0), "nested": {"b": jnp.ones((3, 3))}}
    for step in (1, 2, 3, 4):
        mgr.save(step, tree, extra={"tag": step}, async_=False)
    steps = sorted(
        int(n.split("_")[1]) for n in os.listdir(tmp_path) if n.startswith("step_")
    )
    assert steps == [3, 4]  # keep-2 GC
    restored, extra = mgr.restore(tree)
    assert extra["step"] == 4
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.arange(8.0))


def test_checkpoint_async_then_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3)
    tree = {"w": jnp.ones((64, 64))}
    mgr.save(10, tree, async_=True)
    mgr.wait()
    assert mgr.latest_step() == 10


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    path = str(tmp_path / "ck")
    save_tree(path, {"w": np.ones((4,))})
    with pytest.raises(ValueError):
        restore_tree(path, {"w": jnp.ones((5,))})


def test_straggler_detector():
    det = StragglerDetector(z_threshold=3.0, warmup=5)
    for _ in range(20):
        assert not det.observe(0.1)
    assert det.observe(10.0)  # a 100x step is a straggler
    assert det.flagged == 1


def test_straggler_hook_fires(tmp_path):
    """The detector->callback wiring, fed deterministic step times (wall
    times on a contended CI box are too noisy for timing assertions)."""
    events = []
    cfg = reduced_config("stablelm_3b")
    tcfg = TrainConfig(
        steps=4, checkpoint_every=100, checkpoint_dir=str(tmp_path / "c"),
        attn_impl="xla", straggler_zscore=3.0, straggler_warmup=4,
    )
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4)
    tr = Trainer(
        cfg, AdamWConfig(), tcfg, dcfg, make_host_mesh(),
        straggler_callback=lambda step, dt: events.append((step, dt)),
    )
    # steady steps, then a 100x stall at "step 20"
    tr._observe_step(0, 5.0)  # compile step (ignored by design)
    for s in range(1, 20):
        tr._observe_step(s, 0.1 + 0.001 * (s % 3))
    tr._observe_step(20, 10.0)
    assert events and events[-1][0] == 20
    assert tr.detector.flagged == 1


def test_elastic_restore_across_meshes(tmp_path):
    """Save under one mesh, restore under another (elastic rescale)."""
    tr = _mk_trainer(tmp_path, steps=4, ckpt_every=2)
    out = tr.run()
    # rescale: new mesh with model axis (1 device => (n,1) vs (1,n) layouts)
    new_mesh = make_host_mesh(model=1)
    tr.remesh(new_mesh)
    params_like, opt_like = tr.init_state()
    params, opt, step = tr._restore(params_like, opt_like)
    assert step == 4
    for a, b in zip(jax.tree.leaves(out["params"]), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_grad_accumulation_equivalence(tmp_path):
    """microbatches=2 must match microbatches=1 numerically (fp32)."""
    cfg = dataclasses.replace(reduced_config("stablelm_3b"), dtype=jnp.float32)
    mesh = make_host_mesh()
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=16, global_batch=4, seed=3)
    outs = []
    for mb in (1, 2):
        tcfg = TrainConfig(
            steps=3, checkpoint_every=100, microbatches=mb,
            checkpoint_dir=str(tmp_path / f"mb{mb}"), attn_impl="xla",
        )
        tr = Trainer(cfg, AdamWConfig(lr=1e-3), tcfg, dcfg, mesh)
        outs.append(tr.run()["losses"])
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=2e-4)


def test_data_pipeline_determinism_and_packing():
    cfg = DataConfig(vocab=1000, seq_len=64, global_batch=8, seed=11)
    pipe = SyntheticLM(cfg)
    b1, b2 = pipe.batch(5), pipe.batch(5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert b1["tokens"].shape == (8, 64)
    assert (b1["tokens"] >= 0).all() and (b1["tokens"] < 1000).all()
    # host sharding partitions the global batch
    h0 = SyntheticLM(cfg, host_id=0, n_hosts=2).batch(5)
    h1 = SyntheticLM(cfg, host_id=1, n_hosts=2).batch(5)
    assert h0["tokens"].shape == (4, 64)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_server_continuous_batching():
    cfg = reduced_config("stablelm_3b")
    model = Model(cfg, attn_impl="xla")
    params, _ = model.init(jax.random.PRNGKey(0))
    server = Server(cfg, ServeConfig(batch_slots=2, max_len=32, max_new_tokens=4, eos=-1), params)
    reqs = [
        Request(uid=i, prompt=np.arange(1, 5 + i, dtype=np.int32)) for i in range(5)
    ]
    done = server.serve(reqs)
    assert [c.uid for c in done] == [0, 1, 2, 3, 4]
    for c in done:
        assert 1 <= len(c.tokens) <= 4


def test_server_queues_greedy_steps_ahead_with_the_same_tokens():
    # Without EOS the next decode step is queued before the host reads this
    # one's tokens; an EOS that no token can be makes the host wait for every
    # step.  Both give the same tokens from the same number of steps.
    cfg = reduced_config("stablelm_3b")
    params, _ = Model(cfg).init(jax.random.PRNGKey(0))
    reqs = [Request(uid=i, prompt=np.arange(1, 5 + i, dtype=np.int32)) for i in range(5)]
    tokens, calls = {}, {}
    for eos in (-1, cfg.vocab):
        server = Server(cfg, ServeConfig(batch_slots=2, max_len=32, max_new_tokens=4, eos=eos),
                        params)
        order = calls[eos] = []
        decode, sample, greedy = server._decode, server._sample, server._greedy
        server._decode = lambda *a, f=decode, o=order: o.append("decode") or f(*a)
        server._sample = lambda *a, f=sample, o=order: o.append("sample") or f(*a)
        server._greedy = lambda *a, f=greedy, o=order: o.append("greedy") or f(*a)
        tokens[eos] = [(c.uid, c.tokens) for c in server.serve(reqs)]
    assert tokens[-1] == tokens[cfg.vocab]
    assert all(len(t) == 4 for _, t in tokens[-1])
    assert calls[-1].count("decode") == calls[cfg.vocab].count("decode") > 0
    # one argmax per prefill and per step: the host reads the tokens that
    # were taken before the next step was queued
    for order in calls.values():
        assert order.count("greedy") == order.count("decode") + len(reqs)

    def pairs(order):
        steps = [c for c in order if c != "greedy"]
        return sum(a == b == "decode" for a, b in zip(steps, steps[1:]))

    assert pairs(calls[cfg.vocab]) == 0
    # each request's first decode step queues its second
    assert pairs(calls[-1]) >= 3


@pytest.fixture
def telemetry():
    """The process-wide telemetry, clean and enabled; restored afterwards."""
    from repro import obs

    t = obs.get_telemetry()
    was_enabled, saved = t.enabled, t.export_events(0)
    t.reset()
    t.enable()
    yield t
    t.reset()
    t.adopt(saved)
    t.enabled = was_enabled


def test_server_spans_each_decode_step_and_fill(telemetry):
    cfg = reduced_config("stablelm_3b")
    params, _ = Model(cfg).init(jax.random.PRNGKey(0))
    scfg = ServeConfig(batch_slots=2, max_len=32, max_new_tokens=4, eos=-1)
    reqs = [Request(uid=i, prompt=np.arange(1, 5 + i, dtype=np.int32)) for i in range(5)]

    telemetry.disable()
    off = Server(cfg, scfg, params).serve(reqs)
    assert telemetry.events == []

    telemetry.enable()
    server = Server(cfg, scfg, params)
    decode, decode_calls = server._decode, []
    server._decode = lambda *a: decode_calls.append(1) or decode(*a)
    on = server.serve(reqs)
    assert [(c.uid, c.tokens) for c in on] == [(c.uid, c.tokens) for c in off]

    events = telemetry.events
    by_id = {e.span_id: e for e in events}
    parent = lambda e: by_id[e.parent_id].name  # noqa: E731
    (serve,) = [e for e in events if e.name == "serve"]
    steps = [e for e in events if e.name == "serve.step"]
    fills = [e for e in events if e.name == "serve.fill"]
    assert len(steps) == len(decode_calls) > 0
    assert [e.attrs["step"] for e in steps] == list(range(len(steps)))
    assert all(1 <= e.attrs["active"] <= 2 for e in steps)
    # two fills into the empty batch, one refill for every later request
    assert sorted(e.attrs["uid"] for e in fills) == [0, 1, 2, 3, 4]
    assert {e.attrs["prompt_len"] for e in fills} == {len(r.prompt) for r in reqs}
    assert {e.attrs["slot"] for e in fills} == {0, 1}
    assert sum(e.attrs["refills"] for e in steps) == 3
    assert [parent(e) for e in fills] == ["serve"] * 2 + ["serve.step"] * 3
    assert all(e.parent_id == serve.span_id for e in steps)
    for name, parents in (("serve.decode", {"serve.step"}), ("serve.prefill", {"serve.fill"}),
                          ("serve.insert", {"serve.fill"}),
                          ("serve.sample", {"serve.step", "serve.fill"})):
        spans = [e for e in events if e.name == name]
        assert spans and {parent(e) for e in spans} == parents
    assert sum(1 for e in events if e.name == "serve.decode") == len(steps)
    assert sum(1 for e in events if e.name == "serve.prefill") == len(fills)
    # each refill falls inside the step it follows
    for e in fills[2:]:
        step = by_id[e.parent_id]
        assert step.ts <= e.ts and e.ts + e.dur <= step.ts + step.dur


def test_server_counts_the_kv_blocks_each_decode_step_reads(telemetry, monkeypatch):
    """Each ``serve.step`` holds the share of each cache entry's stored
    blocks that the decode-attention kernel copies for the active slots'
    positions, and the counters sum them: a request of prompt ``P`` and
    ``n`` tokens takes ``n - 1`` decode steps at positions ``P .. P + n - 2``,
    each reading its blocks up to its position (at least one)."""
    from repro import obs
    from repro.kernels import decode_attention

    monkeypatch.setattr(decode_attention, "BLOCK_BYTES", 1)  # one lane of positions a block
    cfg = reduced_config("stablelm_3b")
    params, _ = Model(cfg).init(jax.random.PRNGKey(0))
    scfg = ServeConfig(batch_slots=2, max_len=256, max_new_tokens=4, eos=-1)
    prompts = [1, 125, 127, 200]
    reqs = [Request(uid=i, prompt=np.ones((n,), np.int32)) for i, n in enumerate(prompts)]
    Server(cfg, scfg, params).serve(reqs)

    steps = [e for e in telemetry.events if e.name == "serve.step"]
    assert steps and all(0 < e.attrs["kv_read_share"] <= 1 for e in steps)
    new = scfg.max_new_tokens - 1
    read = sum(max(1, -(-(p + t) // 128)) for p in prompts for t in range(new))
    counters = obs.metrics().snapshot()
    assert counters["serve.kv_blocks_read"] == read
    assert counters["serve.kv_blocks_stored"] == 2 * new * len(prompts)


def test_server_counts_the_bytes_each_decode_step_writes(telemetry):
    """Each ``serve.step`` holds the bytes of the tiles that the row-write
    kernel rewrites, K and V together: every slot's window of 128 positions
    in every layer and head, ``Dh`` padded to whole bfloat16 tiles; the
    counter sums them over the steps, idle slots included."""
    from repro import obs

    cfg = reduced_config("stablelm_3b")
    params, _ = Model(cfg).init(jax.random.PRNGKey(0))
    scfg = ServeConfig(batch_slots=2, max_len=256, max_new_tokens=4, eos=-1)
    reqs = [Request(uid=i, prompt=np.ones((n,), np.int32)) for i, n in enumerate([3, 130, 9])]
    Server(cfg, scfg, params).serve(reqs)

    per_step = 2 * cfg.n_layers * scfg.batch_slots * cfg.n_kv_heads * 16 * 128 * 2
    steps = [e for e in telemetry.events if e.name == "serve.step"]
    assert steps and all(e.attrs["kv_write_bytes"] == per_step for e in steps)
    assert obs.metrics().snapshot()["serve.kv_write_bytes"] == per_step * len(steps)
