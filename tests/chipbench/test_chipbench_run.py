"""The harness end to end on the CPU at test sizes.

The measurement path refuses a CPU device.  Below it, the same plumbing
runs a tiny cell (``fixtures/tiny``) with the chip check skipped: the
window, the spans, the readers, the reference comparison.  The faults a
serving cell can have are planted in the program under the harness, and
each must turn ``correct`` false; so must the control, the float8
reference put in the program's place, against the tiny cell's limits.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import bench

TINY = pathlib.Path(__file__).resolve().parent / "fixtures" / "tiny"


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch, tmp_path):
    # the harness keeps JAX's compile cache where this variable says, and
    # JAX only reads it at import: set here, no cache is written
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    # programs traced by earlier tests in this process could be evicted from
    # JAX's bounded caches in the middle of a window; start from none
    jax.clear_caches()


def _run(root=TINY, workload="tiny.mix", seed=2**31 + 7, seconds=0.3, trace=False, **kw):
    return bench.run_cell(workload, seed, seconds, trace, t_process=time.perf_counter(),
                          root=root, require_tpu=False, **kw)


def test_command_refuses_a_cpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "stablelm_3b.decode_heavy",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=bench.ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]


def test_tiny_cell_runs_through_the_plumbing():
    line = _run()
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] % 4 == 0 and line["attempted"] >= 4
    assert set(line["metrics"]) == {"serve_tokens_per_s", "serve_itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] >= 1
    assert line["compiles_in_window"] == 0
    gap = line["checks"]["logit_gap"]
    assert gap["value"] <= gap["limit"]
    json.dumps(line)


def test_traced_run_reports_per_layer_metrics():
    line = _run(trace=True)
    assert line["correct"] is True
    # host spans give the scheduler's share; a CPU trace has no TPU plane,
    # so the device readers find nothing and stay out of the line
    assert set(line["metrics"]) == {"serve_prefill_share"}
    assert 0 < line["metrics"]["serve_prefill_share"]["value"] < 100
    assert line["device"]["busy_s"] is None and line["device"]["window_s"] > 0


def test_a_new_traffic_file_is_found_by_name(tmp_path):
    root = tmp_path / "root"
    shutil.copytree(TINY, root)
    (root / "chipbench" / "traffic" / "longer.json").write_text(json.dumps({
        "kind": "closed_batches", "why": "one length, three groups",
        "prompt_lengths": {"7": 2}, "groups_per_call": 3, "new_tokens": 3,
    }))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.longer", "config": "tiny", "traffic": "longer",
                              "chips": 1, "why": "added by data alone"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    shutil.copy(root / "chipbench" / "checks" / "tiny.mix.json",
                root / "chipbench" / "checks" / "tiny.longer.json")
    line = _run(root=root, workload="tiny.longer")
    assert line["correct"] is True
    assert line["attempted"] % 6 == 0
    assert line["metrics"]["serve_tokens_per_s"]["value"] > 0


# -- faults planted under the harness -------------------------------------------------


def _state_unchanged(server):
    inner = server._decode

    def decode(params, tokens, state):
        logits, _ = inner(params, tokens, jax.tree.map(jnp.copy, state))
        return logits, state

    server._decode = decode


def _half_the_batch_left_out(server):
    inner = server._decode

    def decode(params, tokens, state):
        logits, state = inner(params, tokens, state)
        half = logits.shape[0] // 2
        return logits.at[half:].set(logits[:half]), state

    server._decode = decode


def _token_altered(server):
    inner = server._sample

    def sample(logits, rng):
        out = np.array(inner(logits, rng))
        out[-1] = (out[-1] + 1) % logits.shape[-1]
        return out

    server._sample = sample


def _last_slot_not_written(server):
    inner = server._insert

    def insert(state, one, slot):
        if int(slot) == server.cfg.batch_slots - 1:
            return state
        return inner(state, one, slot)

    server._insert = insert


@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_batch_left_out, _token_altered,
                                   _last_slot_not_written], ids=lambda f: f.__name__.strip("_"))
def test_a_broken_timed_path_is_not_correct(fault):
    line = _run(fault=fault)
    assert line["correct"] is False
    gap = line["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_the_float8_control_fails_the_limit(seed):
    line = _run(seed=seed, seconds=0.0, control=True)
    # the control, in the program's place, fails; the program on the same
    # sample keeps every limit
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
    assert all(v <= line["checks"][n]["limit"] for n, v in line["program_checks"].items())
