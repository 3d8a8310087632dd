"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

The run wraps its window in a ``chipbench.window`` host annotation and each
call into the program in a ``chipbench.<span>`` annotation
(:class:`chipbench.spans.Spans`).  The profiler puts those host events and
the device's events on one clock.  From a device plane this reads two
lines: ``XLA Modules`` (one event per execution of a jitted program, named
after it) and ``XLA Ops`` (one event per operation inside it).  Busy time
is the union of the operation intervals inside the window; an idle gap is
named after the innermost host span it falls in, or ``host`` where the
host was outside every span.
"""

from __future__ import annotations

import dataclasses
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SPAN_PREFIX = "chipbench."
WINDOW = SPAN_PREFIX + "window"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"

Event = Tuple[str, int, int]  # name, start ns, end ns


@dataclasses.dataclass
class Device:
    name: str
    programs: List[Event]
    ops: List[Event]


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]
    host_spans: List[Event]
    devices: List[Device]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    # -- device time -------------------------------------------------------

    def _inside(self, events: Sequence[Event]) -> List[Event]:
        lo, hi = self.window
        return [(n, max(s, lo), min(e, hi)) for n, s, e in events if e > lo and s < hi]

    def busy_intervals(self, device: Device) -> np.ndarray:
        """Merged (start, end) intervals in which an operation ran."""
        events = self._inside(device.ops or device.programs)
        return _merge([(s, e) for _, s, e in events])

    def busy_s(self) -> Optional[float]:
        """Busy seconds inside the window, the mean over the devices."""
        if not self.devices:
            return None
        per = [_length(self.busy_intervals(d)) for d in self.devices]
        return float(np.mean(per)) * 1e-9

    def program_s(self, fragment: str) -> Optional[float]:
        """Device seconds of the programs whose name holds ``fragment``,
        the mean over the devices; None where no such program ran."""
        per = []
        for d in self.devices:
            hits = [e - s for n, s, e in self._inside(d.programs) if fragment in n]
            if hits:
                per.append(sum(hits))
        return float(np.mean(per)) * 1e-9 if per else None

    def top_programs(self, n: int = 10) -> List[List]:
        totals: Dict[str, float] = {}
        for d in self.devices:
            for name, s, e in self._inside(d.programs):
                totals[name] = totals.get(name, 0.0) + (e - s) * 1e-9 / len(self.devices)
        return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]

    # -- idle time against the host ---------------------------------------------

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest idle gaps of the first device inside the window,
        each named by what the host was doing (innermost span)."""
        if not self.devices:
            return []
        busy = self.busy_intervals(self.devices[0])
        lo, hi = self.window
        edges = np.concatenate([[lo], busy.ravel(), [hi]]).reshape(-1, 2)
        gaps = [(int(s), int(e)) for s, e in edges if e > s]
        gaps.sort(key=lambda g: g[0] - g[1])
        spans = sorted(self.host_spans, key=lambda sp: sp[2] - sp[1])  # innermost first
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) // 2
            name = next((nm for nm, a, b in spans if a <= mid < b and nm != "window"), "host")
            out.append([name, (e - s) * 1e-9])
        return out


def _merge(intervals) -> np.ndarray:
    if not intervals:
        return np.zeros((0, 2), np.int64)
    arr = np.array(sorted(intervals), np.int64)
    out = [list(arr[0])]
    for s, e in arr[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out, np.int64)


def _length(intervals: np.ndarray) -> int:
    return int((intervals[:, 1] - intervals[:, 0]).sum()) if len(intervals) else 0


def find_xplane(directory: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: pathlib.Path) -> Trace:
    """Read an ``.xplane.pb`` file, or the newest one under a directory."""
    from jax.profiler import ProfileData

    path = pathlib.Path(path)
    if path.is_dir():
        path = find_xplane(path)
    return from_profile(ProfileData.from_file(str(path)))


def from_profile(data) -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    spans: List[Event] = []
    devices: List[Device] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            lines = {line.name: line for line in plane.lines}
            if MODULES_LINE not in lines:
                continue
            devices.append(Device(
                plane.name,
                _events(lines[MODULES_LINE]),
                _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
            ))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        start = int(ev.start_ns)
                        spans.append((ev.name[len(SPAN_PREFIX):], start, start + int(ev.duration_ns)))
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if not windows:
        raise ValueError(f"no {WINDOW} annotation in the trace")
    devices.sort(key=lambda d: d.name)
    return Trace(window=windows[0], host_spans=spans, devices=devices)


def _events(line) -> List[Event]:
    out = []
    for ev in line.events:
        start = int(ev.start_ns)
        out.append((ev.name, start, start + int(ev.duration_ns)))
    return out
