"""Training launcher: ``python -m repro.launch.train --arch <id> [options]``.

Real (CPU-scale) runs use the host mesh; the production flags mirror what a
TPU deployment would pass.  ``--smoke`` trains the reduced config of the
chosen architecture — every assigned arch is selectable.
"""

import argparse

from repro.configs import ARCH_IDS, get_config, param_count, reduced_config
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim import AdamWConfig
from repro.runtime import TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS + [a.replace("_", "-") for a in ARCH_IDS])
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()
    enable_compile_cache()

    arch = args.arch.replace("-", "_")
    cfg = reduced_config(arch) if args.smoke else get_config(arch)
    print(f"arch {cfg.name} ({cfg.family}): {param_count(cfg)/1e6:.1f}M params")

    trainer = Trainer(
        model_cfg=cfg,
        opt_cfg=AdamWConfig(lr=args.lr, total_steps=args.steps),
        train_cfg=TrainConfig(
            steps=args.steps,
            microbatches=args.microbatches,
            checkpoint_every=args.ckpt_every,
            checkpoint_dir=args.ckpt_dir,
            remat=args.remat,
            fsdp=args.fsdp,
            attn_impl="xla" if args.seq_len <= 2048 else "chunked",
        ),
        data_cfg=DataConfig(
            vocab=cfg.vocab, seq_len=args.seq_len, global_batch=args.global_batch
        ),
        mesh=make_host_mesh(),
    )
    out = trainer.run()
    losses = out["losses"]
    print(f"trained {out['final_step']} steps; loss {losses[0]:.3f} -> {losses[-1]:.3f}; "
          f"restarts={out['restarts']} stragglers={out['stragglers']}")


if __name__ == "__main__":
    main()
