"""Put the Server's own spans beside the device trace of one traced run.

    python3 chipbench/timeline.py --workload <cell> --seed <n> --seconds <s> --out <dir>
                                  [--root <benchmark root>]

Run from the checkout's root, on the chip.  It enables ``repro.obs``, so
the Server's spans (``serve.step``, ``serve.decode``, ``serve.sample``,
``serve.fill``, ...) go into the profiler's trace as annotations with their
attributes; runs the cell once, traced, keeping the trace under ``<dir>``;
writes the decode step's compiled text beside it (``decode.hlo.txt``); and
prints one JSON line:

* ``result``: the run's result line, metrics and checks;
* ``decode_ms``: device ms per decode step in every named scope
  (:mod:`chipbench.scopes`), the program's own device ms per step, and the
  instructions that take the most of it;
* ``skew_ms``: bounds on how far the trace's device clock runs behind the
  host's, from causality (:func:`skew_bounds_ns`); the lower bound, the
  least shift causality asks for, is the one applied below;
* ``decode_gap_ms``: the median device-idle ms between consecutive decode
  programs whose ``serve.step`` made no refill, after that shift, and how
  the idle time of those gaps splits between ``serve.sample``,
  ``serve.decode`` and neither (:func:`decode_gaps`), also at the upper
  bound;
* ``longest_gaps``: the window's longest idle gaps, each with the innermost
  host span (program's or benchmark's) it fell in.
"""

import os
import pathlib
import sys
import time

_T_PROCESS = time.perf_counter()

# libtpu would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import re  # noqa: E402
from bisect import bisect_left  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

import numpy as np  # noqa: E402

from chipbench import gaps  # noqa: E402
from chipbench.scopes import DECODE_PROGRAM  # noqa: E402

PROGRAM_PREFIX = "serve"

#: (name, start ns, end ns, attributes) of one host annotation
HostSpan = Tuple[str, int, int, Dict[str, object]]


def program_spans(data) -> List[HostSpan]:
    """The program's ``serve`` and ``serve.*`` host annotations of a
    ``jax.profiler.ProfileData``, in order of start."""
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == PROGRAM_PREFIX or name.startswith(PROGRAM_PREFIX + "."):
                    start = int(ev.start_ns)
                    out.append((name, start, start + int(ev.duration_ns), dict(ev.stats)))
    out.sort(key=lambda sp: sp[1])
    return out


def _decode_programs(trace) -> List[Tuple[int, int]]:
    if not trace.devices:
        return []
    return sorted((s, e) for n, s, e in trace.devices[0].programs if DECODE_PROGRAM in n)


def skew_bounds_ns(trace, spans: List[HostSpan]) -> Tuple[Optional[int], Optional[int]]:
    """Bounds on the shift to add to the trace's device times so that host
    and device events fall in causal order; None where no pair is found.

    Each ``serve.decode`` span is taken with the decode program that starts
    nearest its opening, and with the ``serve.sample`` span after it, which
    waits for the first program to start after the decode program (the
    sample's argmax, or softmax).  Lower: neither program starts before the
    span that launched it opens; its slack is the launch latency.  Upper:
    the sample span does not end before its program ends; its slack is the
    result's way back to the host."""
    decode = _decode_programs(trace)
    if not decode:
        return None, None
    starts = [s for s, _ in decode]
    programs = sorted((s, e) for _, s, e in trace.devices[0].programs)
    program_starts = [s for s, _ in programs]
    samples = [(s, e) for n, s, e, _ in spans if n == "serve.sample"]
    sample_starts = [s for s, _ in samples]
    lower = upper = None
    for name, open_ns, close_ns, _ in spans:
        if name != "serve.decode":
            continue
        i = bisect_left(starts, open_ns)
        near = min((j for j in (i - 1, i) if 0 <= j < len(starts)),
                   key=lambda j: abs(starts[j] - open_ns))
        start, end = decode[near]
        leads = [open_ns - start]
        k = bisect_left(sample_starts, close_ns)
        f = bisect_left(program_starts, end)
        if k < len(samples) and f < len(programs):
            leads.append(samples[k][0] - programs[f][0])
            lag = samples[k][1] - programs[f][1]
            upper = lag if upper is None else min(upper, lag)
        lower = max(leads) if lower is None else max(lower, *leads)
    return lower, upper


def _overlap(lo: int, hi: int, intervals: List[Tuple[int, int]]) -> int:
    return sum(max(0, min(hi, e) - max(lo, s)) for s, e in intervals)


def decode_gaps(trace, spans: List[HostSpan], skew: int) -> List[Dict[str, float]]:
    """For each pair of decode programs inside the window that ran in
    consecutive ``serve.step`` spans of one ``serve`` call, the first with
    ``refills == 0``: the device-idle ms between them, and how much of it
    the host spent in ``serve.sample``, in ``serve.decode``, and in neither
    (device times shifted by ``skew``).  The gaps of
    :func:`chipbench.gaps.refill_free_gaps`, chosen by the host's spans
    instead of the device's programs."""
    programs = _decode_programs(trace)
    lo, hi = trace.window
    busy = trace.busy_intervals(trace.devices[0])
    steps = [(s, e, attrs) for n, s, e, attrs in spans if n == "serve.step"]
    step_starts = [s for s, _, _ in steps]
    sample = [(s, e) for n, s, e, _ in spans if n == "serve.sample"]
    decode = [(s, e) for n, s, e, _ in spans if n == "serve.decode"]

    def step_of(t):
        i = bisect_left(step_starts, t + skew + 1) - 1
        return steps[i][2] if i >= 0 and steps[i][0] <= t + skew < steps[i][1] else None

    out = []
    for (s0, e0), (s1, e1) in zip(programs, programs[1:]):
        if s0 < lo or e1 > hi:
            continue
        first, second = step_of(s0), step_of(s1)
        if first is None or second is None or int(first.get("refills", -1)) != 0:
            continue
        if int(second.get("step", -1)) != int(first.get("step", -1)) + 1:
            continue  # the next serve call's first step, after its fills
        idle = [(a + skew, b + skew) for a, b in gaps.idle_pieces(busy, e0, s1)]
        total = sum(b - a for a, b in idle)
        in_sample = sum(_overlap(a, b, sample) for a, b in idle)
        in_decode = sum(_overlap(a, b, decode) for a, b in idle)
        out.append({"idle_ms": total * 1e-6, "sample_ms": in_sample * 1e-6,
                    "decode_ms": in_decode * 1e-6,
                    "neither_ms": (total - in_sample - in_decode) * 1e-6})
    return out


def longest_gaps(trace, spans: List[HostSpan], skew: int, n: int = 5) -> List[List]:
    """The ``n`` longest idle gaps of the first device inside the window,
    each with the innermost host span (program's or benchmark's) holding
    its midpoint once device times are shifted by ``skew``."""
    if not trace.devices:
        return []
    busy = trace.busy_intervals(trace.devices[0])
    lo, hi = trace.window
    edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
    gaps = sorted(((int(s), int(e)) for s, e in edges if e > s), key=lambda g: g[0] - g[1])
    host = [(n_, s, e) for n_, s, e, _ in spans]
    host += [("chipbench." + n_, s, e) for n_, s, e in trace.host_spans if n_ != "window"]
    host.sort(key=lambda sp: sp[2] - sp[1])  # innermost first
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) // 2 + skew
        name = next((nm for nm, a, b in host if a <= mid < b), "host")
        out.append([name, (e - s) * 1e-6])
    return out


def _instruction_line(text: str, name: str) -> str:
    """The start of the instruction's line in the compiled text."""
    m = re.search(rf"^\s+(?:ROOT\s+)?%?{re.escape(name)} = (.*)$", text, re.MULTILINE)
    return m.group(1)[:160] if m else ""


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", type=pathlib.Path, required=True)
    ap.add_argument("--root", type=pathlib.Path, default=_ROOT)
    args = ap.parse_args(argv)

    import jax
    from jax.profiler import ProfileData

    from chipbench import bench, scopes, trace
    from repro import obs
    from repro.models import scopes as model_scopes

    obs.enable()
    try:
        line = bench.run_cell(args.workload, args.seed, args.seconds, True,
                              t_process=_T_PROCESS, root=args.root, trace_dir=args.out)
    except bench.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    obs.disable()

    path = trace.find_xplane(args.out)
    data = ProfileData.from_file(str(path))
    tr = trace.from_profile(data)
    spans = program_spans(data)
    cell = bench.Cell.load(args.workload, args.root)
    text = scopes.cell_decode_text(cell, jax.devices()[0])
    (args.out / "decode.hlo.txt").write_text(text)

    names = scopes.instruction_scopes(text, model_scopes.ALL)
    found = scopes.split(tr, DECODE_PROGRAM, names)
    decode_ms = None
    if found is not None:
        seconds, steps = found
        lo, hi = tr.window
        program_ns = sum(e - s for n, s, e in tr.devices[0].programs
                         if DECODE_PROGRAM in n and s >= lo and e <= hi)
        own, _ = scopes.program_ops(tr, DECODE_PROGRAM)
        top = sorted(own.items(), key=lambda kv: -kv[1])[:16]
        decode_ms = {
            "steps": steps,
            "program_ms": program_ns * 1e-6 / steps,
            "scopes_ms": {k: v * 1e3 / steps for k, v in sorted(seconds.items())},
            "top_instructions": [[n, names.get(n), ns * 1e-6 / steps, _instruction_line(text, n)]
                                 for n, ns in top],
        }
    lower, upper = skew_bounds_ns(tr, spans)
    skew = lower or 0

    def gap_split(shift):
        gaps = decode_gaps(tr, spans, shift) if tr.devices else []
        return gaps, {k: float(sum(g[k] for g in gaps))
                      for k in ("idle_ms", "sample_ms", "decode_ms", "neither_ms")}

    gaps, split = gap_split(skew)
    print(json.dumps({
        "result": line,
        "trace": str(path),
        "program_spans": {n: sum(1 for sp in spans if sp[0] == n)
                          for n in sorted({sp[0] for sp in spans})},
        "decode_ms": decode_ms,
        "skew_ms": {"lower": None if lower is None else lower * 1e-6,
                    "upper": None if upper is None else upper * 1e-6, "applied": skew * 1e-6},
        "decode_gap_ms": float(np.median([g["idle_ms"] for g in gaps])) if gaps else None,
        "gaps_counted": len(gaps),
        "gap_split_ms": split,
        "gap_split_ms_at_upper": None if upper is None else gap_split(upper)[1],
        "longest_gaps": longest_gaps(tr, spans, skew) if tr.devices else [],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
