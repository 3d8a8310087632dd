"""Quickstart: train a small model end-to-end on CPU in ~a minute.

    PYTHONPATH=src python examples/quickstart.py
"""

import tempfile

from repro.configs import reduced_config
from repro.data import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim import AdamWConfig
from repro.runtime import TrainConfig, Trainer


def main() -> None:
    enable_compile_cache()
    cfg = reduced_config("stablelm_3b")
    mesh = make_host_mesh()
    with tempfile.TemporaryDirectory() as tmp:
        trainer = Trainer(
            model_cfg=cfg,
            opt_cfg=AdamWConfig(lr=1e-3, warmup_steps=5, total_steps=60),
            train_cfg=TrainConfig(
                steps=60, checkpoint_every=20, checkpoint_dir=tmp, attn_impl="xla"
            ),
            data_cfg=DataConfig(vocab=cfg.vocab, seq_len=64, global_batch=8),
            mesh=mesh,
        )
        out = trainer.run()
    losses = out["losses"]
    print(f"steps: {out['final_step']}  restarts: {out['restarts']}")
    print(f"loss: {losses[0]:.3f} -> {losses[-1]:.3f}")
    assert losses[-1] < losses[0], "training should reduce loss"
    print("OK")


if __name__ == "__main__":
    main()
