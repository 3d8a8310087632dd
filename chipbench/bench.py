"""The harness: finds a cell's data and code by name and runs it once.

Nothing here belongs to one configuration, traffic mix or metric.  For the
cell named on the command line it reads, under the benchmark root:

* ``BENCHMARK.json``: the cell (configuration, traffic, chips) and the
  metrics it reports;
* the configuration's file (``configs[].file``), whose ``kind`` names the
  module ``chipbench/kinds/<kind>.py`` that sets up, warms up and measures;
* ``chipbench/traffic/<traffic>.json``, read by :mod:`chipbench.traffic`;
* ``chipbench/checks/<cell>.json``: the limits that decide ``correct``;

and for each metric the reader ``chipbench/metrics/<metric>.py``.  A new
cell, mix, configuration or metric is a new file and a new entry.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
import sys
import time
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

import numpy as np

PACKAGE = pathlib.Path(__file__).resolve().parent
ROOT = PACKAGE.parent

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)


class BenchError(RuntimeError):
    """The cell cannot be run as asked (no chip, a malformed file)."""


def seed32(seed: int) -> int:
    """A 32-bit key for JAX from a seed of any size."""
    return int(np.random.SeedSequence(int(seed) % 2**64).generate_state(1)[0])


@dataclasses.dataclass
class Cell:
    name: str
    root: pathlib.Path
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    checks: Dict[str, Any]
    end_to_end: List[str]
    per_layer: List[str]

    @classmethod
    def load(cls, name: str, root: pathlib.Path = ROOT) -> "Cell":
        from chipbench import traffic

        spec = json.loads((root / "BENCHMARK.json").read_text())
        work = next((w for w in spec["workloads"] if w["name"] == name), None)
        if work is None:
            raise BenchError(f"no workload {name!r} in {root / 'BENCHMARK.json'}")
        conf = next(c for c in spec["configs"] if c["name"] == work["config"])

        def reports(metric):
            return "workloads" not in metric or name in metric["workloads"]

        e2e = [m["name"] for m in spec["end_to_end"] if reports(m)]
        per_layer = [
            m["name"] for m in spec["per_layer"]
            if (name in m["workloads"] if "workloads" in m else m["moves"] in e2e)
        ]
        return cls(
            name=name,
            root=root,
            chips=int(work["chips"]),
            config=json.loads((root / conf["file"]).read_text()),
            traffic=traffic.load(root / "chipbench" / "traffic" / f"{work['traffic']}.json"),
            checks=json.loads((root / "chipbench" / "checks" / f"{name}.json").read_text()),
            end_to_end=e2e,
            per_layer=per_layer,
        )


def metric_reader(name: str) -> ModuleType:
    """``chipbench/metrics/<name>.py``, which defines ``UNIT`` and ``read(run)``."""
    path = PACKAGE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name.replace('.', '_')}", path)
    if spec is None or not path.exists():
        raise BenchError(f"no reader {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Run:
    """What a finished run hands to the metric readers."""

    cell: Cell
    dims: Any                       # chipbench.reference.Dims
    peaks: Dict[str, float]
    chips: int
    setup_s: float
    window: tuple                   # (start, end) on the host's perf_counter
    spans: Any                      # chipbench.spans.Spans
    requests: List[tuple]           # (prompt_len, n_out) of each request done in the window
    trace: Any = None               # chipbench.trace.Trace of a traced run

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def spans_in_window(self, name: str):
        return self.spans.within(name, *self.window)


@dataclasses.dataclass
class Outcome:
    run: Run
    correct: bool
    attempted: int
    failed: int
    checks: Dict[str, Dict[str, float]]   # name -> {"value", "limit"}
    memory_peak_bytes: Optional[int]
    notes: Dict[str, Any]


class CompileClock:
    """Seconds and events of tracing, lowering and compiling, from JAX's
    monitoring events (as ``chip_smoke.py`` counts them)."""

    def __init__(self):
        self.seconds = 0.0
        self.events = 0

    def __call__(self, event, secs, **_):
        if event in _COMPILE_EVENTS:
            self.seconds += secs
            self.events += 1


def peaks_for(kind: str) -> Dict[str, float]:
    table = json.loads((PACKAGE / "peaks.json").read_text())
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    *,
    t_process: float,
    root: pathlib.Path = ROOT,
    require_tpu: bool = True,
    trace_dir: Optional[pathlib.Path] = None,
    fault: Optional[Callable] = None,
    control: bool = False,
) -> Dict[str, Any]:
    """Run one cell once and return the result line as a dict.

    ``fault`` breaks the program under test (tests only); ``control`` also
    reads the control's number on the same sample (``chipbench/control.py``).
    """
    cell = Cell.load(workload, root)
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX's first device is {devices[0].platform}")
    if len(devices) < cell.chips:
        raise BenchError(f"{workload} needs {cell.chips} chips; JAX finds {len(devices)}")
    kind = devices[0].device_kind
    peaks = peaks_for(kind) if require_tpu else {}

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program, however quick to compile, comes from the cache in a
    # later run, so set-up does the same work each time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    try:
        kind_module = importlib.import_module(f"chipbench.kinds.{cell.config['kind']}")
        outcome: Outcome = kind_module.run(
            cell, seed=seed, seconds=seconds, trace=trace, trace_dir=trace_dir,
            t_process=t_process, devices=devices[:cell.chips], peaks=peaks,
            clock=clock, fault=fault, control=control,
        )
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)

    names = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for name in names:
        reader = metric_reader(name)
        value = reader.read(outcome.run)
        if value is not None:
            metrics[name] = {"value": float(value), "unit": reader.UNIT}
    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": outcome.memory_peak_bytes,
    }
    line: Dict[str, Any] = {
        "correct": outcome.correct,
        "attempted": outcome.attempted,
        "failed": outcome.failed,
        "metrics": metrics,
        "device": device,
    }
    tr = outcome.run.trace
    if trace and tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        line["breakdown"] = {"device_ops": tr.top_programs(), "idle_gaps": tr.idle_gaps()}
    line.update(outcome.notes)
    line["checks"] = outcome.checks
    return line


def report_checks(checks: Dict[str, Dict[str, float]]) -> None:
    """Each compared number beside its limit, as the last lines on stderr."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)


def main(argv=None, t_process: Optional[float] = None) -> int:
    import argparse

    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", type=pathlib.Path, default=None,
                    help="keep the profiler's trace here (default: a temporary directory)")
    ap.add_argument("--root", type=pathlib.Path, default=ROOT,
                    help="benchmark root holding BENCHMARK.json and the data files")
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                        t_process=t_process, root=args.root, trace_dir=args.trace_dir)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    report_checks(line["checks"])
    print(json.dumps(line), flush=True)
    return 0
