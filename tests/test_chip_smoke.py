"""chip_smoke's phases at reduced sizes on the CPU, and its refusal to run
anywhere but on a TPU."""

import jax
import pytest

import chip_smoke
from repro.configs import reduced_config
from repro.launch.mesh import make_host_mesh
from repro.runtime import Server


def test_serve_phase_reduced():
    out = chip_smoke.phase_serve(
        reduced_config("stablelm_3b"),
        slots=2, max_len=64, n_requests=5, new_tokens=4, lengths=(8, 24),
    )
    assert out["requests"] == 5
    assert out["tokens"] == 5 * 4
    # every Server token, including those of requests in refilled slots,
    # held to the cache-free forward pass
    assert out["checked"] == 5 * 4
    assert out["worst_gap"] <= chip_smoke.TOKEN_MARGIN


def test_serve_phase_catches_a_misplaced_slot(monkeypatch):
    insert = Server._insert_slot
    monkeypatch.setattr(
        Server, "_insert_slot",
        classmethod(lambda cls, state, one, slot: insert(state, one, (slot + 1) % 2)),
    )
    with pytest.raises(chip_smoke.SmokeFailure, match="cache-free"):
        chip_smoke.phase_serve(
            reduced_config("stablelm_3b"),
            slots=2, max_len=64, n_requests=5, new_tokens=4, lengths=(8, 24),
        )


def test_kernels_phase_reduced():
    errs = chip_smoke.phase_kernels(
        reduced_config("stablelm_3b"), reduced_config("mamba2_370m"),
        seq=64, kv_len=128, decode_batch=2, interpret=True,
    )
    assert set(errs) == {"prefill", "decode", "ssd"}
    assert errs["ssd"] <= chip_smoke.SSD_TOL


def test_train_phase_reduced():
    losses = chip_smoke.phase_train(
        reduced_config("stablelm_3b"), make_host_mesh(), steps=2, batch=4, seq=32
    )
    assert len(losses) == 2


def test_train_config_keeps_published_widths():
    cfg = chip_smoke.train_config()
    full = chip_smoke.get_config("stablelm_3b")
    assert cfg.n_layers == 4
    assert (cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab) == (
        full.d_model, full.n_heads, full.d_ff, full.vocab
    )


def test_main_refuses_the_cpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out
    assert "needs a TPU" in out.err
