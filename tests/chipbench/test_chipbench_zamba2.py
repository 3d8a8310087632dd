"""The Zamba2 serving kind through the harness on the CPU at test sizes.

``fixtures/tiny_hybrid`` is a cell of ``kind: serve_zamba2`` at the layer
of ``zamba2_7b`` cut to test widths (9 layers, shared blocks at 2, 4 and 7,
2 memory blocks, 2 SSM groups, adapter rank 4).  The plumbing runs with the
chip check skipped, as for ``fixtures/tiny``: the window, the spans, the
readers, the comparison with ``chipbench/reference_zamba2.py``.  A decode
step that leaves the state unchanged must turn ``correct`` false, and so
must the float8 control in the program's place.

The tiny cell's limits sit between the readings on the CPU over seeds 1-7:
the program's largest ``logit_gap`` 0.0139 and ``mean_logit_gap`` 0.0012,
the control's smallest 0.058 and 0.0103.
"""

import pathlib
import time

import jax
import jax.numpy as jnp
import pytest

from chipbench import bench

TINY = pathlib.Path(__file__).resolve().parent / "fixtures" / "tiny_hybrid"


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch, tmp_path):
    # the harness keeps JAX's compile cache where this variable says
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    jax.clear_caches()


def _run(seed=2**31 + 7, seconds=0.3, trace=False, **kw):
    return bench.run_cell("tiny_hybrid.mix", seed, seconds, trace, t_process=time.perf_counter(),
                          root=TINY, require_tpu=False, **kw)


def _state_unchanged(server):
    inner = server._decode

    def decode(params, tokens, state):
        logits, _ = inner(params, tokens, jax.tree.map(jnp.copy, state))
        return logits, state

    server._decode = decode


def test_tiny_hybrid_cell_runs_through_the_plumbing():
    line = _run()
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] % 4 == 0 and line["attempted"] >= 4
    assert set(line["metrics"]) == {"serve_tokens_per_s", "serve_itl_p95_ms", "setup_s"}
    assert line["compiles_in_window"] == 0
    assert line["checks"]["logit_gap"]["value"] <= line["checks"]["logit_gap"]["limit"]


def test_traced_hybrid_run_reads_nothing_without_a_chip():
    # a CPU trace has no TPU plane: the device readers stay out of the line
    line = _run(trace=True)
    assert line["correct"] is True
    assert line["metrics"] == {}


def test_a_decode_that_keeps_the_state_is_not_correct():
    line = _run(fault=_state_unchanged)
    assert line["correct"] is False
    assert line["checks"]["logit_gap"]["value"] > line["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_fails_the_hybrid_limits(seed):
    line = _run(seed=seed, seconds=0.0, control=True)
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
    assert all(v <= line["checks"][n]["limit"] for n, v in line["program_checks"].items())


def test_a_program_without_the_zamba2_layer_is_refused(monkeypatch):
    """The parent program, whose ``ModelConfig`` lacks the Zamba2 fields,
    fails at once with the harness's error, before any weights are made."""
    import dataclasses

    from chipbench.kinds import serve_zamba2
    from repro.models import ModelConfig

    fields = [f for f in dataclasses.fields(ModelConfig) if f.name != "hybrid_layer_ids"]
    monkeypatch.setattr(dataclasses, "fields", lambda cls: fields)
    with pytest.raises(bench.BenchError, match="no Zamba2 layer"):
        serve_zamba2.program_config(bench.Cell.load("tiny_hybrid.mix", TINY).config)
