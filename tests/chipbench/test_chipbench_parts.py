"""The benchmark's yardstick on the CPU: traffic, FLOP and byte counts, the
plain reference, the metric readers and the trace reducer."""

import collections
import json
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import flops, serve_check, trace, traffic
from chipbench.bench import ROOT, metric_reader
from chipbench.reference import Dims, logits_at, make_weights

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
MIXES = sorted((ROOT / "chipbench" / "traffic").glob("*.json"))


def _dims(**kw):
    base = dict(layers=2, d_model=64, heads=4, kv_heads=2, head_dim=16, d_ff=128, vocab=256,
                rope_theta=10_000.0, norm_eps=1e-6, tie_embeddings=False)
    return Dims(**dict(base, **kw))


# -- traffic ---------------------------------------------------------------------


@pytest.mark.parametrize("path", MIXES, ids=lambda p: p.stem)
def test_traffic_repeats_for_a_seed_and_differs_across_seeds(path):
    mix = traffic.load(path)
    big = 2**31 + 12345
    a = traffic.call(mix, 50304, big, 3)
    b = traffic.call(mix, 50304, big, 3)
    c = traffic.call(mix, 50304, big + 1, 3)
    assert len(a) == len(traffic.group_lengths(mix)) * mix["groups_per_call"]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(len(x) == len(y) and np.array_equal(x, y) for x, y in zip(a, c))
    # every seed sends the same lengths, group by group, in another order
    group = len(traffic.group_lengths(mix))
    for prompts in (a, c):
        for lo in range(0, len(prompts), group):
            assert sorted(len(p) for p in prompts[lo:lo + group]) == traffic.group_lengths(mix)
    assert all(p.min() >= 1 and p.max() < 50304 for p in a)


def test_traffic_refuses_an_unknown_kind(tmp_path):
    bad = tmp_path / "open.json"
    bad.write_text(json.dumps({"kind": "poisson", "prompt_lengths": {"8": 1}}))
    with pytest.raises(ValueError):
        traffic.load(bad)


# -- FLOP and byte counts against hand counts --------------------------------------

STABLELM = Dims(layers=32, d_model=2560, heads=32, kv_heads=32, head_dim=80, d_ff=6912,
                vocab=50304, rope_theta=1e4, norm_eps=1e-6, tie_embeddings=False)
GRANITE = Dims(layers=36, d_model=4096, heads=32, kv_heads=8, head_dim=128, d_ff=14336,
               vocab=49152, rope_theta=1e7, norm_eps=1e-5, tie_embeddings=False)


def test_parameter_counts_by_hand():
    # stablelm_3b: 4 * 2560^2 attention + 3 * 2560 * 6912 MLP per block
    assert flops.block_params(STABLELM) == 32 * (4 * 2560 * 2560 + 3 * 2560 * 6912)
    # as run: blocks, their norm scales, untied embedding and head, final norm
    norms = 32 * 2 * 2560 + 2560
    assert flops.block_params(STABLELM) + 2 * flops.head_params(STABLELM) + norms == 2_795_276_800
    # granite_8b: q and o 4096^2, k and v 4096 x 1024 (8 kv heads of 128)
    per = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert flops.block_params(GRANITE) == 36 * per == 36 * 218_103_808
    assert flops.head_params(GRANITE) == 4096 * 49152


def test_prefill_and_decode_counts_by_hand():
    m = STABLELM
    n = flops.block_params(m)
    # causal attention of a 512 prompt: 4 * H * Dh * L * (512 * 513 / 2)
    attn = 4 * 32 * 80 * 32 * (512 * 513 // 2)
    assert flops.prefill_flops(m, 512) == 2 * n * 512 + attn + 2 * 2560 * 50304
    kv = 2 * 32 * 32 * 80 * 2  # bytes of one position's keys and values, all layers
    assert flops.kv_bytes_per_position(m) == kv == 327_680
    weights = 2 * (n + 2560 * 50304 + 32 * 2 * 2560 + 2560)
    assert flops.prefill_bytes(m, 512) == weights + 2 * 512 * 2560 + 512 * kv
    # a decode step at positions 100 and 200 reads 101 and 201 cache entries
    step = flops.decode_bytes(m, [100, 200])
    assert step == weights + 2 * 2 * 2560 + (101 + 201) * kv
    assert flops.decode_flops(m, [100]) == 2 * (n + 2560 * 50304) + 4 * 32 * 80 * 32 * 101
    # a request of prompt 10 and 4 outputs decodes at positions 10, 11, 12
    assert list(flops.request_decode_positions(10, 4)) == [10, 11, 12]
    ops, moved = flops.decode_window(m, [(10, 4)], steps=3)
    assert ops == flops.decode_flops(m, [10, 11, 12])
    assert moved == 3 * (weights) + 3 * 2 * 2560 + (11 + 12 + 13) * kv


# -- the plain reference against the program ---------------------------------------


def _program(dims, dtype):
    import dataclasses

    from repro.configs import get_config
    from repro.models import Model

    cfg = dataclasses.replace(
        get_config("stablelm_3b"), n_layers=dims.layers, d_model=dims.d_model,
        n_heads=dims.heads, n_kv_heads=dims.kv_heads, d_ff=dims.d_ff, vocab=dims.vocab,
        rope_theta=dims.rope_theta, dtype=dtype,
    )
    return cfg, Model(cfg)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_reference_weights_are_the_programs(dtype):
    dims = _dims()
    _, model = _program(dims, dtype)
    key = jax.random.PRNGKey(7)
    params = model.init(key)[0]
    w = make_weights(dims, key, dtype)
    pairs = {"embed": params["embed"], "head": params["lm_head"],
             "final_ln": params["final_ln"], **params["layers"]}
    for name, got in pairs.items():
        assert w[name].dtype == got.dtype
        assert np.array_equal(np.asarray(w[name], np.float32), np.asarray(got, np.float32)), name


def test_reference_logits_match_the_program_in_float32():
    from repro.models import transformer

    dims = _dims()
    cfg, model = _program(dims, jnp.float32)
    key = jax.random.PRNGKey(3)
    params = model.init(key)[0]
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 24), 1, dims.vocab)
    h, _ = transformer.forward(cfg, params, tokens)
    want = np.asarray(model.logits(params, h))
    at = jnp.broadcast_to(jnp.arange(24)[None], (2, 24))
    got = np.asarray(logits_at(dims, make_weights(dims, key, jnp.float32), tokens, at))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_fp8_control_departs_from_the_reference():
    dims = _dims()
    w = make_weights(dims, jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 16), 1, dims.vocab)
    at = jnp.broadcast_to(jnp.arange(16)[None], (2, 16))
    ref = np.asarray(logits_at(dims, w, tokens, at))
    ctl = np.asarray(logits_at(dims, w, tokens, at, "fp8"))
    err = np.abs(ctl - ref).max() / np.abs(ref).max()
    assert 1e-3 < err < 0.5


def test_sample_is_a_block_of_consecutive_requests_with_the_longest():
    served = [serve_check.Served(np.ones(n, np.int32), np.ones(4, np.int32))
              for n in [5, 9, 5, 9, 5, 9, 5, 30]]
    for seed in range(20):
        chosen = serve_check.pick(served, 2, seed)
        assert chosen[1] == chosen[0] + 1 and chosen[0] % 2 == 0
        assert 7 in chosen
    blocks = list(serve_check.blocks([served[i] for i in (0, 1, 7)], rows=2))
    assert [b[0].shape for b in blocks] == [(2, 128), (2, 128)]
    assert [b[3] for b in blocks] == [2, 1]


# -- the metric readers declare what BENCHMARK.json says ------------------------------


@pytest.mark.parametrize("metric", SPEC["end_to_end"] + SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_each_metric_has_its_reader(metric):
    reader = metric_reader(metric["name"])
    assert reader.UNIT == metric["unit"]
    assert reader.SOURCE == metric["source"]
    if "layer" in metric:
        assert reader.LAYER == metric["layer"]
        assert reader.MOVES == metric["moves"]


def test_benchmark_file_keeps_its_contract():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert name.match(m["name"]) and 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    for m in SPEC["per_layer"]:
        assert name.match(m["name"]) and m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for w in SPEC["workloads"]:
        assert name.match(w["name"]) and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").exists()
        assert (ROOT / "chipbench" / "checks" / f"{w['name']}.json").exists()
    for c in SPEC["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["source"].startswith(c["source"])
        assert sorted(conf["reduced"]) == sorted(c["reduced"])


# -- the trace reducer ---------------------------------------------------------------
#
# A hand-made trace in the profiler's own format, laid out as a chip trace
# is (one TPU plane with "XLA Modules" and "XLA Ops" lines, the benchmark's
# annotations on a host thread), so every number below is worked out by
# hand.  Times in microseconds.

_HOST = [("window", 0, 100), ("call", 5, 95), ("prefill", 10, 30), ("insert", 30, 32),
         ("decode", 40, 60), ("decode", 60, 80)]
_MODULES = [("jit__prefill_fn", 12, 28), ("jit__insert_slot", 31, 32),
            ("jit__decode_step", 42, 58), ("jit__decode_step", 62, 78),
            ("jit__decode_step", 99, 120)]  # runs past the window's end
_OPS = [("fusion.1", 12, 20), ("fusion.2", 20, 28), ("dynamic-update-slice", 31, 32),
        ("fusion.3", 42, 50), ("while", 50, 58), ("fusion.3", 62, 70), ("while", 70, 78),
        ("fusion.3", 99, 120)]


def _line(line_id, name, events, names):
    out = [f'lines {{ id: {line_id} name: "{name}" timestamp_ns: 0']
    for ev, start, end in events:
        out.append(f"events {{ metadata_id: {names.setdefault(ev, len(names) + 1)} "
                   f"offset_ps: {start * 10**6} duration_ps: {(end - start) * 10**6} }}")
    return " ".join(out) + " }"


def _plane(plane_id, name, lines):
    names = {}
    body = " ".join(_line(i + 1, ln, ev, names) for i, (ln, ev) in enumerate(lines))
    meta = " ".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
                    for n, k in names.items())
    return f'planes {{ id: {plane_id} name: "{name}" {body} {meta} }}'


@pytest.fixture(scope="module")
def handmade():
    from jax.profiler import ProfileData

    text = " ".join([
        _plane(1, "/device:TPU:0", [("XLA Modules", _MODULES), ("XLA Ops", _OPS)]),
        _plane(2, "/host:CPU", [("python", [("chipbench." + n, a, b) for n, a, b in _HOST])]),
    ])
    return trace.from_profile(ProfileData.from_text_proto(text))


def test_trace_reads_devices_programs_and_spans(handmade):
    assert [d.name for d in handmade.devices] == ["/device:TPU:0"]
    assert handmade.window == (0, 100_000) and handmade.window_s == pytest.approx(1e-4)
    assert sorted({n for n, _, _ in handmade.host_spans}) == sorted({n for n, _, _ in _HOST})


def test_trace_busy_share_programs_and_gaps(handmade):
    # busy: 12-28, 31-32, 42-58, 62-78 and 99-100 inside the window
    assert handmade.busy_s() == pytest.approx(50e-6)
    assert handmade.program_s("_decode_step") == pytest.approx(33e-6)
    assert handmade.program_s("_prefill_fn") == pytest.approx(16e-6)
    assert handmade.program_s("no_such_program") is None
    top = handmade.top_programs()
    assert [n for n, _ in top] == ["jit__decode_step", "jit__prefill_fn", "jit__insert_slot"]
    # idle: 0-12 (host in "call"), 28-31 ("prefill" ends at 30: its midpoint
    # 29.5 is inside), 32-42 ("call"), 58-62 ("decode", the second starts at
    # 60) and 78-99 ("call"), longest first
    gaps = handmade.idle_gaps()
    assert [g[0] for g in gaps] == ["call", "call", "call", "decode", "prefill"]
    assert [round(g[1] * 1e6, 6) for g in gaps] == [21, 12, 10, 4, 3]


# A trace recorded on a TPU v5e by ``chipbench/record_trace.py``: three
# calls, each one ``prefill`` and four ``decode`` programs and a 20 ms host
# pause, inside the benchmark's spans.

_CHIP_TRACE = pathlib.Path(__file__).resolve().parent / "fixtures" / "chip_trace"


@pytest.fixture(scope="module")
def recorded():
    return trace.load(_CHIP_TRACE)


def test_recorded_chip_trace_programs_and_spans(recorded):
    assert [d.name for d in recorded.devices] == ["/device:TPU:0"]
    spans = collections.Counter(n for n, _, _ in recorded.host_spans)
    assert spans == {"window": 1, "call": 3, "prefill": 3, "decode": 12}
    programs = collections.Counter(n.split("(")[0] for n, _, _ in recorded.devices[0].programs)
    assert programs == {"jit_prefill": 3, "jit_decode": 12}
    assert recorded.window_s == pytest.approx(0.07106079)


def test_recorded_chip_trace_busy_programs_and_gaps(recorded):
    # the first prefill ran before the window's annotation opened (below),
    # so two of three prefills and all twelve decodes count
    assert recorded.busy_s() == pytest.approx(4.1701e-05)
    top = recorded.top_programs()
    assert [n.split("(")[0] for n, _ in top] == ["jit_prefill", "jit_decode"]
    assert recorded.program_s("jit_decode") == pytest.approx(1.4982e-05)
    gaps = recorded.idle_gaps()
    # the three host pauses, each 20 ms and a little of the call around it
    assert [g[0] for g in gaps[:3]] == ["call"] * 3
    assert all(0.020 < g[1] < 0.023 for g in gaps[:3])
    assert all(g[1] < 0.001 for g in gaps[3:])


def test_recorded_chip_trace_device_leads_the_host_by_a_millisecond(recorded):
    # each program begins 0.8-1.1 ms before the host span that launched it:
    # the reducer takes both on one clock, which moves a window's edges and
    # the gaps' names by that much
    for kind in ("prefill", "decode"):
        host = sorted(s for n, s, _ in recorded.host_spans if n == kind)
        device = sorted(s for n, s, _ in recorded.devices[0].programs if f"jit_{kind}(" in n)
        lead = [(h - d) * 1e-6 for h, d in zip(host, device)]
        assert len(lead) == len(host) and all(0.8 < ms < 1.1 for ms in lead)


def test_interval_arithmetic():
    a = trace._merge([(0, 10), (5, 20), (30, 40)])
    assert a.tolist() == [[0, 20], [30, 40]]
    assert trace._length(a) == 30
