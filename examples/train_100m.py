"""End-to-end driver: train a ~100M-parameter model for a few hundred steps.

A scaled-down granite-style dense transformer (the paper's training-side
substrate exercised for real): deterministic synthetic corpus, AdamW with
cosine schedule, gradient accumulation, periodic async checkpoints, fault
tolerance on, straggler detector armed.

    PYTHONPATH=src python examples/train_100m.py --steps 300
"""

import argparse
import dataclasses
import time

import jax

from repro.configs import get_config
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import ModelConfig
from repro.optim import AdamWConfig
from repro.runtime import TrainConfig, Trainer


def model_100m() -> ModelConfig:
    # ~100M params: 12L x 512 x 8H, ff 2048, 32k vocab
    return dataclasses.replace(
        get_config("granite_8b"),
        name="granite_100m",
        n_layers=12,
        d_model=512,
        n_heads=8,
        n_kv_heads=4,
        d_ff=2048,
        vocab=32_000,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt", default="/tmp/repro_100m")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = model_100m()
    from repro.configs import param_count

    print(f"model: {cfg.name}, {param_count(cfg)/1e6:.0f}M params")
    mesh = make_host_mesh()
    trainer = Trainer(
        model_cfg=cfg,
        opt_cfg=AdamWConfig(lr=6e-4, warmup_steps=30, total_steps=args.steps),
        train_cfg=TrainConfig(
            steps=args.steps,
            microbatches=2,
            checkpoint_every=100,
            checkpoint_dir=args.ckpt,
            attn_impl="chunked",
            remat="dots",
            log_every=20,
        ),
        data_cfg=DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch),
        mesh=mesh,
        straggler_callback=lambda s, dt: print(f"  [straggler] step {s}: {dt:.2f}s"),
    )
    t0 = time.time()
    out = trainer.run()
    dt = time.time() - t0
    losses = out["losses"]
    tok_s = args.steps * args.batch * args.seq / dt
    print(f"done in {dt:.0f}s ({tok_s:.0f} tok/s on {jax.default_backend()})")
    for i in range(0, len(losses), max(1, len(losses) // 10)):
        print(f"  step {i:4d}  loss {losses[i]:.3f}")
    print(f"  final loss {losses[-1]:.3f} (started {losses[0]:.3f})")


if __name__ == "__main__":
    main()
