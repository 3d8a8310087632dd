"""The split of a program's device time by named scope, and the Server's
spans beside the device trace (``chipbench/scopes.py``,
``chipbench/timeline.py``), on hand-made traces, on CPU-compiled text and on
a CPU profiler trace."""

import collections
import contextlib
import pathlib
import time

import jax
import numpy as np
import pytest

from chipbench import bench, gaps, scopes, timeline
from chipbench.trace import Device, Trace
from repro import obs
from repro.models import scopes as model_scopes

TINY = pathlib.Path(__file__).resolve().parent / "fixtures" / "tiny"


@pytest.fixture(autouse=True)
def _no_persistent_cache(monkeypatch, tmp_path):
    # as in test_chipbench_run.py: no compile cache is written, and no
    # program traced by an earlier test is evicted mid-window
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jax_cache"))
    jax.clear_caches()


# -- instruction names to scopes --------------------------------------------------------

_TEXT = """HloModule jit__decode_step, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %dynamic-update-slice.1 = f32[4]{0} dynamic-update-slice(%param_0), metadata={op_name="jit(_decode_step)/while/body/closed_call/kv_carry/scatter" stack_frame_id=3}
  ROOT %convert.1 = f32[4]{0} convert(%dynamic-update-slice.1)
}

%fused_computation.3 (param_0.2: f32[4]) -> (f32[4], f32[4]) {
  %param_0.2 = f32[4]{0} parameter(0)
  %exp.3 = f32[4]{0} exponential(%param_0.2), metadata={op_name="jit(_decode_step)/while/body/closed_call/attention/exp"}
  %mul.3 = f32[4]{0} multiply(%param_0.2, %param_0.2), metadata={op_name="jit(_decode_step)/while/body/closed_call/mlp/mul"}
  %convert.3 = f32[4]{0} convert(%mul.3)
  ROOT %tuple.3 = (f32[4]{0}, f32[4]{0}) tuple(%exp.3, %convert.3)
}

%fused_computation.2 (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  %exp.2 = f32[4]{0} exponential(%param_0.1), metadata={op_name="jit(_decode_step)/while/body/closed_call/attention/while/body/exp"}
  ROOT %add.2 = f32[4]{0} add(%exp.2, %exp.2), metadata={op_name="jit(_decode_step)/while/body/closed_call/attention/add"}
}

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.1 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %fusion.7 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(_decode_step)/while/body/closed_call"}
  %fusion.8 = (f32[4]{0}, f32[4]{0}) fusion(%fusion.2), kind=kLoop, calls=%fused_computation.3
  %copy.3 = f32[4]{0} copy(%fusion.2), metadata={op_name="jit(_decode_step)/while/body/closed_call/attention/while/body/mlp/dot_general"}
  ROOT %copy.4 = f32[4]{0} copy(%copy.3)
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %while.5 = f32[4]{0} while(%x), condition=%cond, body=%body, metadata={op_name="jit(_decode_step)/while"}
  ROOT %fusion.6 = f32[4]{0} fusion(%while.5), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(_decode_step)/lm_head/dot_general"}
}
"""


def test_instruction_scopes_take_the_innermost_and_look_into_fusions():
    found = scopes.instruction_scopes(_TEXT, model_scopes.ALL)
    # own metadata first, innermost scope of its op_name
    assert found["copy.3"] == "mlp" and found["fusion.6"] == "lm_head"
    # a fusion with no metadata of its own takes the scope its root is
    # computed in, through fused instructions that have none
    assert found["fusion.1"] == "kv_carry" and found["fusion.2"] == "attention"
    # ... and none where its root is computed from two scopes
    assert found["fusion.8"] == scopes.UNSCOPED
    # no metadata, or metadata in no scope, fusions included: unscoped
    assert found["copy.4"] == found["while.5"] == found["p"] == scopes.UNSCOPED
    assert found["fusion.7"] == scopes.UNSCOPED
    assert found["exp.2"] == "attention"


# -- a program's operation time by scope ------------------------------------------------

_OP = "%{} = f32[4]{{0}} {}(%x)"


def _trace(extra=()):
    step = [("while.5", 0, 80), ("fusion.1", 10, 30), ("copy.3", 30, 50), ("copy.4", 60, 70),
            ("fusion.6", 80, 100)] + list(extra)
    ops = []
    for base in (100, 300, 500, 950):
        ops += [(_OP.format(n, n.split(".")[0]), base + s, base + e) for n, s, e in step]
    programs = [("jit__decode_step(7)", 100, 200), ("jit__decode_step(7)", 300, 400),
                ("jit__prefill_fn(3)", 500, 600),
                ("jit__decode_step(7)", 950, 1050)]  # runs past the window's end
    return Trace(window=(0, 1000), host_spans=[("window", 0, 1000)],
                 devices=[Device("/device:TPU:0", programs, ops)])


def test_split_counts_each_instant_once_for_the_innermost_operation():
    seconds, runs = scopes.split(_trace(), "_decode_step",
                                 scopes.instruction_scopes(_TEXT, model_scopes.ALL))
    assert runs == 2
    # per run: the loop's own 80 ns less 50 ns of operations inside it, and
    # the unscoped copy's 10 ns
    assert {k: round(v * 1e9) for k, v in seconds.items()} == {
        "kv_carry": 40, "mlp": 40, "unscoped": 80, "lm_head": 40}


def test_split_is_none_where_the_text_misses_over_one_percent():
    names = scopes.instruction_scopes(_TEXT, model_scopes.ALL)
    # an operation the text lacks, nested in the loop: 1 ns of 100 passes,
    # 2 ns of 100 does not
    assert scopes.split(_trace([("fusion.99", 55, 56)]), "_decode_step", names) is not None
    assert scopes.split(_trace([("fusion.99", 55, 57)]), "_decode_step", names) is None
    assert scopes.split(_trace(), "_no_such_program", names) is None


def _run(trace):
    cell = bench.Cell.load("tiny.mix", TINY)
    return bench.Run(cell=cell, dims=None, peaks={}, chips=1, setup_s=0.0, window=(0.0, 1.0),
                     spans=None, requests=[], trace=trace)


def test_decode_readers_split_the_compiled_decode_step():
    cell = bench.Cell.load("tiny.mix", TINY)
    names = scopes.instruction_scopes(scopes.cell_decode_text(cell), model_scopes.ALL)
    pick = {scope: next(n for n, s in names.items() if s == scope)
            for scope in ("kv_carry", "kv_write", "attention", "unscoped")}
    # two decode programs of 100 ns: 10 ns of kv_write, 20 of kv_carry, 30 of
    # attention and 40 unscoped each
    ops, t = [], 0
    for base in (100, 300):
        t = base
        for scope, ns in (("kv_write", 10), ("kv_carry", 20), ("attention", 30),
                          ("unscoped", 40)):
            ops.append((f"%{pick[scope]} = f32[4]{{0}} op(%x)", t, t + ns))
            t += ns
    trace = Trace(window=(0, 1000), host_spans=[("window", 0, 1000)], devices=[
        Device("/device:TPU:0", [("jit__decode_step(1)", 100, 200),
                                 ("jit__decode_step(1)", 300, 400)], ops)])
    run = _run(trace)
    read = {scope: bench.metric_reader(f"decode_ms.{scope}").read(run)
            for scope in ("kv_carry", "kv_write", "attention", "unscoped")}
    assert read == pytest.approx({"kv_write": 1e-5, "kv_carry": 2e-5, "attention": 3e-5,
                                  "unscoped": 4e-5})


def test_decode_readers_read_nothing_from_a_program_without_scopes(monkeypatch):
    # the program as it was before the scopes: the reader stays out of the line
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    assert bench.metric_reader("decode_ms.kv_carry").read(_run(_trace())) is None
    monkeypatch.setattr(scopes, "model_scope_names", lambda: None)
    assert bench.metric_reader("decode_ms.unscoped").read(_run(_trace())) is None
    assert bench.metric_reader("decode_ms.attention").read(_run(None)) is None


# -- the Server's spans against the device trace ----------------------------------------

# Times in microseconds.  Three decode steps, each followed by its argmax
# program; the second step refills a slot.  The host opens each
# serve.decode span 1 us after the trace shows its program starting, and the
# second serve.sample span 2 us after its argmax: a device clock at least
# 2 us behind the host's.  Each serve.sample span ends 6 us after its argmax:
# at most 6 us.
_STEPS = [(10, 40, 0), (40, 75, 1), (75, 100, 0)]   # serve.step: start, end, refills
_DECODE_SPANS = [(11, 30), (41, 65), (76, 90)]
_SAMPLE_SPANS = [(30, 38), (65, 70), (90, 98)]
_PROGRAMS = [(10, 28), (40, 63), (75, 88)]           # decode programs, device clock
_ARGMAX = [(31, 32), (63, 64), (91, 92)]


def _timeline_trace():
    us = 1000
    programs = [("jit__decode_step(2)", s * us, e * us) for s, e in _PROGRAMS]
    programs += [("jit__argmax(4)", s * us, e * us) for s, e in _ARGMAX]
    ops = [(f"%fusion.{i} = f32[4]{{0}} fusion(%x)", s, e) for i, (_, s, e) in enumerate(programs)]
    spans = [("serve.step", s * us, e * us, {"step": k, "refills": r})
             for k, (s, e, r) in enumerate(_STEPS)]
    spans += [("serve.decode", s * us, e * us, {}) for s, e in _DECODE_SPANS]
    spans += [("serve.sample", s * us, e * us, {}) for s, e in _SAMPLE_SPANS]
    spans.sort(key=lambda sp: sp[1])
    trace = Trace(window=(0, 100 * us), host_spans=[("window", 0, 100 * us)],
                  devices=[Device("/device:TPU:0", programs, ops)])
    return trace, spans


def test_skew_is_bracketed_by_causality():
    trace, spans = _timeline_trace()
    assert timeline.skew_bounds_ns(trace, spans) == (2000, 6000)
    no_samples = [sp for sp in spans if sp[0] != "serve.sample"]
    assert timeline.skew_bounds_ns(trace, no_samples) == (1000, None)
    assert timeline.skew_bounds_ns(trace, [sp for sp in spans if sp[0] != "serve.decode"]) == (
        None, None)


def test_decode_gaps_follow_steps_without_refills_and_split_by_span():
    trace, spans = _timeline_trace()
    gaps = timeline.decode_gaps(trace, spans, 1000)
    # only the first gap: its step made no refill, and the third program has
    # no successor.  Shifted by 1 us, the first program ends at 29 and the
    # second starts at 41; argmax runs 32-33.  Idle: 29-32 and 33-41, 11 us;
    # serve.decode holds 29-30, serve.sample 30-32 and 33-38, neither 38-41.
    assert gaps == [pytest.approx({"idle_ms": 0.011, "sample_ms": 0.007, "decode_ms": 0.001,
                                   "neither_ms": 0.003})]
    # the longest gap, 64-75 on the device's clock, is in the second step
    longest = timeline.longest_gaps(trace, spans, 1000, n=2)
    assert longest[0][0] == "serve.step" and longest[0][1] == pytest.approx(0.011)


def _with_refill(trace):
    """The timeline trace with the refill its second step made: a prefill
    at 66-69 us and a slot insert at 70-71 us, device clock."""
    us = 1000
    device = trace.devices[0]
    refill = [("jit__prefill_fn(5)", 66 * us, 69 * us), ("jit__insert_slot(6)", 70 * us, 71 * us)]
    ops = [(f"%fusion.{50 + i} = f32[4]{{0}} fusion(%x)", s, e)
           for i, (_, s, e) in enumerate(refill)]
    return Trace(window=trace.window, host_spans=trace.host_spans,
                 devices=[Device(device.name, device.programs + refill, device.ops + ops)])


def test_decode_gap_reader_skips_the_gaps_that_hold_a_refill():
    trace = _with_refill(_timeline_trace()[0])
    # the first gap alone: 28-31 and 32-40 us idle around the argmax; the
    # second holds the refill, and the third program has no successor
    assert gaps.decode_gap_ms(trace) == [pytest.approx(0.011)]
    assert gaps.refill_free_gaps(trace) == [[(28000, 31000), (32000, 40000)]]
    # without the refill's programs the second gap counts too: 64-75 us
    assert gaps.decode_gap_ms(_timeline_trace()[0]) == [pytest.approx(0.011),
                                                        pytest.approx(0.011)]
    # a decode program past the window's end pairs with nothing
    late = _with_refill(_timeline_trace()[0])
    late.window = (0, 87000)
    assert gaps.decode_gap_ms(late) == [pytest.approx(0.011)]
    reader = bench.metric_reader("decode_gap_ms")
    assert reader.read(_run(trace)) == pytest.approx(0.011)
    assert reader.read(_run(None)) is None
    assert reader.read(_run(Trace(window=(0, 1), host_spans=[], devices=[]))) is None


def test_idle_pieces_leave_out_every_busy_interval():
    busy = np.array([[0, 10], [15, 20], [30, 40]], np.int64)
    assert gaps.idle_pieces(busy, 5, 35) == [(10, 15), (20, 30)]
    assert gaps.idle_pieces(busy, 10, 15) == [(10, 15)]
    assert gaps.idle_pieces(busy, 40, 50) == [(40, 50)]
    assert gaps.idle_pieces(busy, 31, 39) == []
    assert gaps.idle_pieces(np.zeros((0, 2), np.int64), 1, 3) == [(1, 3)]


def test_program_spans_come_back_from_a_profiler_trace(tmp_path):
    from jax.profiler import ProfileData

    from chipbench import trace as trace_mod

    tel = obs.get_telemetry()
    was_enabled, saved = tel.enabled, tel.export_events(0)
    tel.enable()
    try:
        line = bench.run_cell("tiny.mix", 2**31 + 7, 0.3, True, t_process=time.perf_counter(),
                              root=TINY, require_tpu=False, trace_dir=tmp_path)
    finally:
        tel.reset()
        tel.adopt(saved)
        tel.enabled = was_enabled
    assert line["correct"] is True
    data = ProfileData.from_file(str(trace_mod.find_xplane(tmp_path)))
    spans = timeline.program_spans(data)
    count = lambda name: sum(1 for sp in spans if sp[0] == name)  # noqa: E731
    # the window's requests, each filled once; a decode span in every step
    assert count("serve.fill") == count("serve.prefill") == line["attempted"]
    assert count("serve.step") == count("serve.decode") > 0
    steps = [sp for sp in spans if sp[0] == "serve.step"]
    assert all({"step", "active", "refills"} <= set(attrs) for _, _, _, attrs in steps)
    assert all(a[1] <= b[1] for a, b in zip(spans, spans[1:]))


# A trace recorded on a TPU v5e by ``chipbench/timeline.py`` on the tiny
# cell (``fixtures/tiny``, two calls: 12 decode steps, 8 fills), with the
# decode step's compiled text beside it.

_SCOPED = pathlib.Path(__file__).resolve().parent / "fixtures" / "scoped_trace"


@pytest.fixture(scope="module")
def scoped():
    from jax.profiler import ProfileData

    from chipbench import trace as trace_mod

    data = ProfileData.from_file(str(_SCOPED / "tiny.xplane.pb"))
    return trace_mod.from_profile(data), timeline.program_spans(data), \
        (_SCOPED / "decode.hlo.txt").read_text()


def test_recorded_scoped_trace_matches_its_compiled_text(scoped):
    tr, _, text = scoped
    names = scopes.instruction_scopes(text, model_scopes.ALL)
    own, runs = scopes.program_ops(tr, "_decode_step")
    assert runs == 12
    matched = sum(ns for n, ns in own.items() if n in names)
    assert matched >= 0.99 * sum(own.values())
    seconds, steps = scopes.split(tr, "_decode_step", names)
    assert steps == 12
    assert set(seconds) == {"qkv", "kv_write", "kv_carry", "attention", "mlp", "lm_head",
                            "unscoped"}
    # the scopes hold every operation of the program, and no more
    assert sum(seconds.values()) == pytest.approx(sum(own.values()) * 1e-9)


def test_recorded_scoped_trace_carries_the_servers_spans(scoped):
    tr, spans, _ = scoped
    count = collections.Counter(sp[0] for sp in spans)
    assert count == {"serve": 2, "serve.step": 12, "serve.decode": 12, "serve.sample": 20,
                     "serve.fill": 8, "serve.prefill": 8, "serve.insert": 8}
    assert sum(sp[3]["refills"] for sp in spans if sp[0] == "serve.step") == 4
    # the device's clock runs 1.1-1.9 ms behind the host's here, as the
    # benchmark's own spans showed on the first recorded trace
    lower, upper = timeline.skew_bounds_ns(tr, spans)
    assert 1.0e6 < lower < upper < 2.0e6
    found = timeline.decode_gaps(tr, spans, lower)
    # two calls of six steps: ten pairs of steps in one call, two of them
    # with refills
    assert len(found) == 8 and all(g["idle_ms"] > 0 for g in found)
    for g in found:
        assert g["sample_ms"] + g["decode_ms"] + g["neither_ms"] == pytest.approx(g["idle_ms"])


def test_recorded_scoped_trace_gaps_by_program_are_the_gaps_by_span(scoped):
    # the decode gap reader picks its gaps from the device's programs alone;
    # the Server's serve.step spans pick the same eight, whatever the shift
    tr, spans, _ = scoped
    by_program = gaps.decode_gap_ms(tr)
    for shift in timeline.skew_bounds_ns(tr, spans):
        by_span = [g["idle_ms"] for g in timeline.decode_gaps(tr, spans, shift)]
        assert by_program == pytest.approx(by_span)
    assert bench.metric_reader("decode_gap_ms").read(_run(tr)) == pytest.approx(
        float(np.median(by_program)))
