"""Telemetry-layer tests: span nesting and exception safety, the
disabled-mode no-op contract, the metrics registry (and its pool
export/merge), and both exporters (JSONL + Chrome trace)."""

import json

import pytest

from repro import obs
from repro.core.kernelgen import paper_kernel
from repro.core.regdem import RegDemOptions, demote
from repro.obs import (
    NULL_SPAN,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Telemetry,
    chrome_trace,
    hit_rate,
    to_jsonl,
)


@pytest.fixture
def tel():
    """The process-wide telemetry, enabled and clean; prior state (other
    tests may have recorded spans) is restored afterwards."""
    t = obs.get_telemetry()
    was_enabled = t.enabled
    saved_events = t.export_events(0)
    saved_metrics = t.registry.export()
    t.reset()
    t.enable()
    yield t
    t.reset()
    t.adopt(saved_events)
    t.registry.merge(saved_metrics)
    t.enabled = was_enabled


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_spans_nest_and_link_parents(tel):
    with obs.span("outer", depth=0) as outer:
        with obs.span("inner") as inner:
            with obs.span("leaf"):
                pass
    by_name = {e.name: e for e in tel.events}
    assert set(by_name) == {"outer", "inner", "leaf"}
    assert by_name["outer"].parent_id is None
    assert by_name["inner"].parent_id == outer.span_id
    assert by_name["leaf"].parent_id == inner.span_id
    # inner spans close (and record) before their parents
    assert [e.name for e in tel.events] == ["leaf", "inner", "outer"]
    assert by_name["outer"].attrs == {"depth": 0}
    assert all(e.dur >= 0 for e in tel.events)


def test_span_set_attaches_midflight_attrs(tel):
    with obs.span("work", kernel="nn") as sp:
        sp.set(outcome="cached", n=3)
    (rec,) = tel.events
    assert rec.attrs == {"kernel": "nn", "outcome": "cached", "n": 3}


def test_exception_closes_every_open_span(tel):
    with pytest.raises(ValueError):
        with obs.span("outer"):
            with obs.span("inner"):
                raise ValueError("boom")
    by_name = {e.name: e for e in tel.events}
    assert set(by_name) == {"outer", "inner"}
    assert by_name["inner"].attrs["error"] == "ValueError"
    assert by_name["outer"].attrs["error"] == "ValueError"
    # the thread-local stack is coherent again: a new span is a root
    with obs.span("after"):
        pass
    assert tel.events[-1].parent_id is None


def test_leaked_span_does_not_corrupt_the_stack(tel):
    """A span entered by hand and never exited is popped by its parent's
    exit, keeping the timeline coherent."""
    outer = obs.span("outer")
    outer.__enter__()
    leaked = obs.span("leaked")
    leaked.__enter__()  # never exited
    outer.__exit__(None, None, None)
    assert [e.name for e in tel.events] == ["outer"]
    with obs.span("next"):
        pass
    assert tel.events[-1].parent_id is None


def test_disabled_span_is_the_shared_noop(tel):
    obs.disable()
    s = obs.span("anything", k=1)
    assert s is NULL_SPAN
    with s as inner:
        inner.set(a=1)  # chainable no-op
    assert tel.event_count() == 0
    # the telemetry-object path takes the same shortcut
    assert tel.span("x") is NULL_SPAN


def test_disabled_mode_records_nothing_at_volume(tel):
    obs.disable()
    for _ in range(10_000):
        with obs.span("hot"):
            pass
    assert tel.event_count() == 0
    assert len(tel.registry) == 0


def test_reset_drops_events_but_not_the_switch(tel):
    with obs.span("x"):
        pass
    tel.registry.counter("c").inc()
    tel.reset()
    assert tel.event_count() == 0
    assert len(tel.registry) == 0
    assert tel.enabled


# ---------------------------------------------------------------------------
# spans on the JAX profiler's timeline
# ---------------------------------------------------------------------------


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; keeps what a span
    asked of it."""

    opened = []

    def __init__(self, name, **attrs):
        self.name, self.attrs, self.state = name, dict(attrs), "made"
        _Annotation.opened.append(self)

    def __enter__(self):
        self.state = "open"
        return self

    def __exit__(self, *exc):
        self.state = "closed"

    def set_metadata(self, **attrs):
        self.attrs.update(attrs)


@pytest.fixture
def annotations(monkeypatch):
    import jax

    _Annotation.opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Annotation)
    return _Annotation.opened


def test_enabled_span_opens_a_profiler_annotation_with_its_attributes(tel, annotations):
    with obs.span("serve.step", step=3, active=2) as sp:
        assert [(a.name, a.attrs, a.state) for a in annotations] == [
            ("serve.step", {"step": 3, "active": 2}, "open")]
        sp.set(refills=1)
    (ann,) = annotations
    assert ann.state == "closed" and ann.attrs == {"step": 3, "active": 2, "refills": 1}
    assert tel.events[-1].attrs == ann.attrs


def test_disabled_span_opens_no_profiler_annotation(tel, annotations):
    tel.disable()
    with obs.span("serve.step", step=3) as sp:
        sp.set(refills=1)
    assert sp is NULL_SPAN and annotations == [] and tel.events == []


def test_span_without_jax_imported_opens_no_annotation(tel, annotations, monkeypatch):
    import sys

    monkeypatch.delitem(sys.modules, "jax")
    with obs.span("translate", kernel="nn"):
        pass
    assert annotations == [] and [e.name for e in tel.events] == ["translate"]


def test_enabled_spans_land_in_a_profiler_trace(tel, tmp_path):
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with obs.span("serve.step", step=7, active=2) as sp:
            with obs.span("serve.decode"):
                jax.numpy.ones(4).block_until_ready()
            sp.set(refills=0)
    (path,) = tmp_path.rglob("*.xplane.pb")
    found = {ev.name: dict(ev.stats) for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for ev in line.events if ev.name.startswith("serve.")}
    assert found["serve.step"] == {"step": 7, "active": 2, "refills": 0}
    assert "serve.decode" in found


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_counter_monotonic():
    c = Counter()
    c.inc()
    c.inc(4)
    assert c.snapshot() == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_last_write():
    g = Gauge()
    g.set(3.5)
    g.set(1.0)
    assert g.snapshot() == 1.0


def test_histogram_percentiles_exact():
    h = Histogram()
    for v in range(1, 101):
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["min"] == 1.0 and snap["max"] == 100.0
    assert snap["mean"] == pytest.approx(50.5)
    assert snap["p50"] == 51.0  # nearest-rank over 1..100
    assert snap["p99"] == 99.0


def test_histogram_ring_trims_samples_not_books():
    h = Histogram(max_samples=4)
    for v in [100.0, 1.0, 2.0, 3.0, 4.0]:  # 100.0 falls out of the ring
        h.observe(v)
    assert h.count == 5
    assert h.total == 110.0
    assert h.vmax == 100.0  # extrema are exact even after trimming
    assert h.percentile(50) == 3.0  # percentiles see only the resident ring


def test_registry_get_or_create_and_type_guard():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    reg.gauge("g").set(2)
    with pytest.raises(TypeError):
        reg.counter("g")
    snap = reg.snapshot()
    assert list(snap) == sorted(snap)
    assert snap["a"] == 0 and snap["g"] == 2


def test_registry_export_merge_roundtrip():
    worker = MetricsRegistry()
    worker.counter("hits").inc(3)
    worker.gauge("entries").set(7)
    h = worker.histogram("ms")
    h.max_samples = 2  # force ring trimming so merge must restore the books
    for v in [50.0, 1.0, 2.0]:
        h.observe(v)

    parent = MetricsRegistry()
    parent.counter("hits").inc(1)
    parent.merge(worker.export())
    assert parent.counter("hits").value == 4  # counters add
    assert parent.gauge("entries").value == 7  # gauges last-write
    merged = parent.histogram("ms")
    assert merged.count == 3 and merged.total == 53.0 and merged.vmax == 50.0


def test_hit_rate_convention():
    assert hit_rate(3, 1) == 0.75
    assert hit_rate(0, 5) == 0.0
    # zero traffic has no meaningful rate: explicit error unless the caller
    # (a display/stats path) opts into a default
    with pytest.raises(ValueError, match="no cache accesses"):
        hit_rate(0, 0)
    assert hit_rate(0, 0, default=0.0) == 0.0


# ---------------------------------------------------------------------------
# pool-worker span exchange
# ---------------------------------------------------------------------------


def test_export_since_mark_and_adopt():
    worker = Telemetry()
    worker.enable()
    with worker.span("inherited"):
        pass
    mark = worker.event_count()
    with worker.span("task"):
        pass
    exported = worker.export_events(mark)
    assert [e.name for e in exported] == ["task"]

    parent = Telemetry()
    parent.enable()
    with parent.span("local"):
        pass
    assert parent.adopt(exported) == 1
    assert [e.name for e in parent.events] == ["local", "task"]
    assert parent.snapshot()["spans"] == 2


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def _record_timeline(tel):
    with obs.span("root", kind="test"):
        with obs.span("child-a"):
            pass
        with obs.span("child-b"):
            pass
    tel.registry.counter("n").inc(2)


def test_chrome_trace_is_valid_and_monotonic(tel):
    _record_timeline(tel)
    trace = json.loads(json.dumps(chrome_trace(tel)))  # JSON-serializable
    events = trace["traceEvents"]
    assert len(events) == 3
    rows = {}
    for e in events:
        assert e["ph"] == "X"
        assert e["dur"] >= 0
        assert e["ts"] >= 0
        row = (e["pid"], e["tid"])
        assert e["ts"] >= rows.get(row, 0.0)  # monotonic within each row
        rows[row] = e["ts"]
    assert min(e["ts"] for e in events) == 0.0  # rebased to the earliest span
    assert {e["name"] for e in events} == {"root", "child-a", "child-b"}


def test_jsonl_lines_parse_and_end_with_metrics(tel):
    _record_timeline(tel)
    lines = to_jsonl(tel).splitlines()
    parsed = [json.loads(ln) for ln in lines]
    assert all(p["kind"] == "span" for p in parsed[:-1])
    assert parsed[-1]["kind"] == "metrics"
    assert parsed[-1]["metrics"]["n"] == 2
    span_names = {p["name"] for p in parsed[:-1]}
    assert span_names == {"root", "child-a", "child-b"}


def test_write_trace_dispatches_on_extension(tel, tmp_path):
    _record_timeline(tel)
    chrome = tmp_path / "t.json"
    jsonl = tmp_path / "t.jsonl"
    assert obs.write_trace(str(chrome)) == "chrome"
    assert obs.write_trace(str(jsonl)) == "jsonl"
    assert "traceEvents" in json.loads(chrome.read_text())
    assert all(json.loads(ln) for ln in jsonl.read_text().splitlines())


# ---------------------------------------------------------------------------
# instrumentation integration: the pipeline actually emits spans + metrics
# ---------------------------------------------------------------------------


def test_pipeline_emits_spans_and_metrics(tel):
    demote(paper_kernel("nn"), 32, options=RegDemOptions())
    names = [e.name for e in tel.events]
    assert "pipeline" in names
    assert any(n.startswith("pass:") for n in names)
    # every pass span is a child of the pipeline span
    by_id = {e.span_id: e for e in tel.events}
    pipe = next(e for e in tel.events if e.name == "pipeline")
    for e in tel.events:
        if e.name.startswith("pass:"):
            assert by_id[e.parent_id].span_id == pipe.span_id
    snap = tel.registry.snapshot()
    assert snap["pipeline.runs"] >= 1
    assert snap["pipeline.passes"] >= 1
    assert any(k.startswith("pass:") or k.startswith("pass.") for k in snap)
