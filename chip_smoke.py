"""Smoke run of the main paths on a TPU: serving, the Pallas kernels, training.

    python chip_smoke.py              # one chip: phases serve, kernels, train
    python chip_smoke.py --chips 4    # the sharded Trainer path, and one chip

Weights and data are random, made from ``--seed``.  Each phase prints, on
its own lines, its wall time, the seconds JAX spent tracing, lowering and
compiling in it, and the device's peak memory so far in the process (the
counter cannot be reset, so the phases run from the smallest expected peak
up).  The last line of standard output is one JSON object naming the
device.  The script exits non-zero, and prints no such line, unless JAX's
first device is a TPU, or if any check of any phase fails.

Phases (one chip), in the order they run:

* ``kernels`` — ``ops.flash_attention`` at stablelm_3b attention widths
  (prefill 2048, decode against 4096) and ``ops.mamba2_ssd`` at
  mamba2_370m SSD widths, compiled (``interpret=False``, a
  ``tpu_custom_call`` in the HLO), against ``kernels/ref.py`` at
  ``highest`` matmul precision.
* ``train`` — 3 ``Trainer`` steps of stablelm_3b at published widths cut to
  4 layers, global batch 8 at sequence 1024, on ``make_host_mesh()``.
* ``serve`` — stablelm_3b at its published widths and depth through
  ``Server``: 8 slots, ``max_len`` 2048, 16 prompts of 64 to 1024 tokens,
  32 new tokens each.  Checks completion and token range, and holds every
  token the Server emitted to the finite logits of a cache-free forward
  pass over the same tokens (:data:`TOKEN_MARGIN`).

``--chips 4`` runs only the ``train`` model and batch on a
(data=1, model=4) mesh and then on a one-device mesh, and checks that the
losses agree (:data:`LOSS_RTOL`).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import math
import pathlib
import sys
import tempfile
import time

_SRC = str(pathlib.Path(__file__).resolve().parent / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.data import DataConfig  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.models import Model, ModelConfig, transformer  # noqa: E402
from repro.optim import AdamWConfig  # noqa: E402
from repro.runtime import ServeConfig, Server, TrainConfig, Trainer  # noqa: E402
from repro.runtime.serving import Request  # noqa: E402

#: Server tokens vs a cache-free forward pass, in standard deviations of the
#: cache-free logit row: how far the logit of the token the Server chose may
#: trail the row's max.  Sampling is greedy, so the Server's token is the
#: argmax of logits it computed through its KV cache.  Those and the
#: cache-free logits run the same bf16 weights and differ only in summation
#: order (attention over a 2048-slot cache or over the sequence, one-row or
#: many-row matmuls): a relative L2 error up to 2**-5, room for eight bf16
#: roundings (2**-8) compounding through the layers.  Over a 50304-entry
#: row the largest error is about 4.5 times the RMS error, and the chosen
#: token can trail the max by twice that: 2 * 4.5 * 2**-5 = 0.28.  A token
#: read from a stale slot, a wrong cache position or a NaN row (argmax then
#: returns the first NaN's index) is unrelated to the row and trails its
#: max by about 4.
TOKEN_MARGIN = 0.3
#: bf16 flash attention vs the f32 reference: the output is bf16 (2**-9
#: relative) and the kernel may feed bf16-rounded probabilities to the MXU.
ATTN_TOL = 2e-2
#: f32 SSD vs the f32 sequential recurrence: the kernel's dots run at
#: HIGHEST precision, so only exp/summation order differ (~1e-5); a wrong
#: decay or a dropped chunk is O(1).
SSD_TOL = 2e-3
#: 4-chip vs 1-chip training loss, relative: bf16 partial sums are reduced
#: across chips in another order, which moves a mean over 8192 tokens by
#: ~1e-3; a missing or doubled reduction moves it by O(1).
LOSS_RTOL = 1e-2

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _free(tree) -> None:
    """Release the device buffers of a phase's large pytrees now, rather
    than whenever the objects holding them are collected."""
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array):
            x.delete()
    gc.collect()


@contextlib.contextmanager
def _phase(name: str, devices):
    compile_s = [0.0]

    def listen(event, secs, **_):
        if event in _COMPILE_EVENTS:
            compile_s[0] += secs

    jax.monitoring.register_event_duration_secs_listener(listen)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    wall = time.perf_counter() - t0
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        peaks.append("not reported" if peak is None else f"{peak / 2**30:.3f} GiB")
    print(f"[{name}] wall {wall:.2f} s")
    print(f"[{name}] compile (trace+lower+compile) {compile_s[0]:.2f} s")
    print(f"[{name}] peak_bytes_in_use {', '.join(peaks)}", flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_serve(
    cfg: ModelConfig,
    *,
    slots: int = 8,
    max_len: int = 2048,
    n_requests: int = 16,
    new_tokens: int = 32,
    lengths=(64, 128, 256, 512, 1024),
    seed: int = 0,
) -> dict:
    model = Model(cfg)
    params = jax.jit(lambda k: model.init(k)[0])(jax.random.PRNGKey(seed))
    server = Server(
        cfg,
        ServeConfig(batch_slots=slots, max_len=max_len, max_new_tokens=new_tokens,
                    eos=-1, seed=seed),
        params,
    )
    rng = np.random.default_rng(seed)
    lens = rng.permutation([lengths[i % len(lengths)] for i in range(n_requests)])
    reqs = [
        Request(uid=i, prompt=rng.integers(1, cfg.vocab, size=int(n), dtype=np.int32))
        for i, n in enumerate(lens)
    ]
    done = server.serve(reqs)
    _check([c.uid for c in done] == list(range(n_requests)), "not every request completed")
    for c in done:
        _check(len(c.tokens) == new_tokens, f"request {c.uid}: {len(c.tokens)} tokens")
        _check(all(0 <= t < cfg.vocab for t in c.tokens), f"request {c.uid}: token out of vocab")

    # every token the Server emitted against a cache-free forward pass over
    # prompt + tokens[:-1], read at the position that produced it.  With no
    # EOS every request runs new_tokens steps, so uids 0..slots-1 go into the
    # empty batch and every later uid into a slot that a finished request
    # freed, all through `Server._insert`.
    seq_len = max(len(r.prompt) for r in reqs) + new_tokens - 1

    @jax.jit
    def cache_free(p, toks, first):
        h = transformer.forward(cfg, p, toks, attn_impl=model.attn_impl)[0]
        at = first[:, None] + jnp.arange(new_tokens)[None]
        h = jnp.take_along_axis(h, at[..., None], axis=1)
        return model.logits(p, h).astype(jnp.float32)

    gaps = []
    for lo in range(0, n_requests, slots):
        group = reqs[lo:lo + slots]
        # one shape for every group; the padding sits after each row's last
        # read position, where causal attention cannot see it
        toks = np.zeros((slots, seq_len), np.int32)
        first = np.zeros((slots,), np.int32)
        for i, r in enumerate(group):
            toks[i, :len(r.prompt) + new_tokens - 1] = np.concatenate(
                [r.prompt, done[r.uid].tokens[:-1]]
            )
            first[i] = len(r.prompt) - 1
        want = np.asarray(cache_free(params, toks, first))[:len(group)]
        _check(np.isfinite(want).all(), "non-finite cache-free logits")
        for r, w in zip(group, want):
            got = np.asarray(done[r.uid].tokens)
            gaps.append((w.max(-1) - w[np.arange(new_tokens), got]) / w.std(-1))
    gaps = np.stack(gaps)
    worst = float(gaps.max())
    print(f"[serve] {len(done)} requests x {new_tokens} tokens; Server tokens vs cache-free "
          f"logits: {int((gaps == 0).sum())} of {gaps.size} the argmax, worst gap "
          f"{worst:.3e} std (limit {TOKEN_MARGIN}, request {int(gaps.max(-1).argmax())})")
    _check(worst <= TOKEN_MARGIN, f"Server tokens disagree with the cache-free pass: {worst:.3e}")
    _free(params)
    return {"requests": len(done), "tokens": sum(len(c.tokens) for c in done),
            "checked": int(gaps.size),
            "worst_gap": worst}


def _attention_ref(q, k, v, qp, kp):
    """``ref.attention_reference`` on model-layout (B, S, H, Dh) operands."""
    b, sq, hq, dh = q.shape

    def heads(x):  # (B, S, H, Dh) -> (B*Hq, S, Dh), GQA groups repeated
        x = jnp.repeat(x.transpose(0, 2, 1, 3), hq // x.shape[2], axis=1)
        return x.reshape(b * hq, -1, dh)

    def pos(p):
        return jnp.repeat(p[:, None, :], hq, axis=1).reshape(b * hq, -1)

    out = ref.attention_reference(heads(q), heads(k), heads(v), pos(qp), pos(kp))
    return out.reshape(b, hq, sq, dh).transpose(0, 2, 1, 3)


def _max_err(got, want) -> float:
    """Largest |got - want| / (1 + |want|), the allclose(atol=rtol=t) measure."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    _check(np.isfinite(got).all(), "non-finite kernel output")
    return float(np.max(np.abs(got - want) / (1.0 + np.abs(want))))


def _compiled(fn, interpret: bool, *args, **static):
    """AOT-compile ``fn`` (a jitted kernel wrapper); on a chip, require the
    Pallas kernel in the compiled HLO."""
    compiled = fn.lower(*args, interpret=interpret, **static).compile()
    if not interpret:
        _check("tpu_custom_call" in compiled.as_text(), f"{fn.__name__}: no tpu_custom_call")
    return compiled


def phase_kernels(
    attn_cfg: ModelConfig,
    ssd_cfg: ModelConfig,
    *,
    seq: int = 2048,
    kv_len: int = 4096,
    decode_batch: int = 8,
    interpret: bool = False,
    seed: int = 0,
) -> dict:
    ks = jax.random.split(jax.random.PRNGKey(seed), 12)
    hq, hkv, dh = attn_cfg.n_heads, attn_cfg.n_kv_heads, attn_cfg.dh
    errs = {}
    highest = jax.default_matmul_precision("highest")

    def attention_case(name, b, sq, skv, q_pos, kk):
        q = jax.random.normal(kk[0], (b, sq, hq, dh), jnp.float32).astype(jnp.bfloat16)
        k = jax.random.normal(kk[1], (b, skv, hkv, dh), jnp.float32).astype(jnp.bfloat16)
        v = jax.random.normal(kk[2], (b, skv, hkv, dh), jnp.float32).astype(jnp.bfloat16)
        kp = jnp.broadcast_to(jnp.arange(skv, dtype=jnp.int32)[None], (b, skv))
        out = _compiled(ops.flash_attention, interpret, q, k, v, q_pos, kp)(q, k, v, q_pos, kp)
        with highest:
            want = jax.jit(_attention_ref)(q, k, v, q_pos, kp)
        errs[name] = _max_err(out, want)
        print(f"[kernels] flash_attention {name} B{b} Sq{sq} Skv{skv} H{hq} dh{dh}: "
              f"max err {errs[name]:.3e} (limit {ATTN_TOL:.0e})")
        _check(errs[name] <= ATTN_TOL, f"flash_attention {name} disagrees with ref")

    prefill_pos = jnp.arange(seq, dtype=jnp.int32)[None]
    attention_case("prefill", 1, seq, seq, prefill_pos, ks[0:3])
    # each decode slot sits at its own length inside the kv_len cache
    decode_pos = jax.random.randint(ks[3], (decode_batch, 1), kv_len // 2, kv_len, jnp.int32)
    attention_case("decode", decode_batch, 1, kv_len, decode_pos, ks[4:7])

    h, p, n, q = ssd_cfg.ssm_heads, ssd_cfg.ssm_head_dim, ssd_cfg.ssm_state, ssd_cfg.ssm_chunk
    x = jax.random.normal(ks[7], (1, seq, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[8], (1, seq, h)))
    a = -jnp.exp(jax.random.normal(ks[9], (h,)) * 0.3)
    bm = jax.random.normal(ks[10], (1, seq, n)) * 0.4
    cm = jax.random.normal(ks[11], (1, seq, n)) * 0.4
    y, h_last = _compiled(ops.mamba2_ssd, interpret, x, dt, a, bm, cm, chunk=q)(x, dt, a, bm, cm)
    with highest:
        y_ref, h_ref = jax.jit(ref.ssd_reference)(x, dt, a, bm, cm)
    errs["ssd"] = max(_max_err(y, y_ref), _max_err(h_last, h_ref))
    print(f"[kernels] mamba2_ssd S{seq} H{h} P{p} N{n} chunk{q}: "
          f"max err {errs['ssd']:.3e} (limit {SSD_TOL:.0e})")
    _check(errs["ssd"] <= SSD_TOL, "mamba2_ssd disagrees with ref")
    return errs


def phase_train(
    cfg: ModelConfig,
    mesh,
    *,
    steps: int = 3,
    batch: int = 8,
    seq: int = 1024,
    remat: str = "dots",
    seed: int = 0,
) -> list:
    with tempfile.TemporaryDirectory() as ckpt:
        trainer = Trainer(
            cfg,
            AdamWConfig(lr=1e-4, warmup_steps=1, total_steps=steps),
            TrainConfig(steps=steps, checkpoint_every=steps, checkpoint_dir=ckpt,
                        remat=remat, seed=seed),
            DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed),
            mesh,
        )
        out = trainer.run(max_restarts=0)
    losses = out["losses"]
    print(f"[train] mesh {dict(mesh.shape)}: losses {losses}")
    _check(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    _check(all(math.isfinite(x) for x in losses), "non-finite loss")
    _free((out["params"], out["opt_state"]))
    return losses


def train_config() -> ModelConfig:
    """stablelm_3b at every published width, cut to 4 of its 32 layers so
    that weights and Adam state fit one chip."""
    return dataclasses.replace(get_config("stablelm_3b"), n_layers=4)


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} devices", file=sys.stderr)
        return 2
    print(f"compile cache: {enable_compile_cache()}")
    print(f"devices: {len(devices)} x {dev.device_kind}")

    if args.chips == 4:
        with _phase("train4", devices[:4]):
            sharded = phase_train(train_config(), make_host_mesh(model=4, devices=devices[:4]),
                                  seed=args.seed)
        with _phase("train1", devices[:1]):
            single = phase_train(train_config(), make_host_mesh(devices=devices[:1]),
                                 seed=args.seed)
        worst = max(abs(a - b) / abs(b) for a, b in zip(sharded, single))
        print(f"[train4] 4-chip vs 1-chip loss rel diff {worst:.3e} (limit {LOSS_RTOL:.0e})")
        _check(worst <= LOSS_RTOL, "4-chip and 1-chip losses disagree")
    else:
        one = devices[:1]
        with _phase("kernels", one):
            phase_kernels(get_config("stablelm_3b"), get_config("mamba2_370m"), seed=args.seed)
        with _phase("train", one):
            phase_train(train_config(), make_host_mesh(devices=one), seed=args.seed)
        with _phase("serve", one):
            phase_serve(get_config("stablelm_3b"), seed=args.seed)

    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
