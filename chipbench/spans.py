"""Host spans the benchmark records around its calls into the program.

Each span is kept in memory as ``(name, start, end, size)`` on the host's
``perf_counter`` clock and is also written into the profiler's trace as a
``chipbench.<name>`` annotation, so that a traced run can name the device's
idle gaps by what the host was doing.
"""

from __future__ import annotations

import contextlib
import time
from typing import Callable, List, NamedTuple, Optional

import jax

from chipbench.trace import SPAN_PREFIX


class Span(NamedTuple):
    name: str
    start: float
    end: float
    size: int  # what the call worked on, e.g. a prompt's length; 0 if none


class Spans:
    def __init__(self):
        self.items: List[Span] = []

    @contextlib.contextmanager
    def span(self, name: str, size: int = 0):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + name):
            yield
        self.items.append(Span(name, t0, time.perf_counter(), size))

    def wrap(self, name: str, fn: Callable, ready: Optional[Callable] = None,
             size: Optional[Callable] = None) -> Callable:
        """``fn`` inside a span; the span ends once ``ready(result)`` is on
        the device (where given), so its end is the result's time."""

        def inner(*args, **kwargs):
            with self.span(name, size(*args) if size else 0):
                out = fn(*args, **kwargs)
                if ready is not None:
                    ready(out).block_until_ready()
            return out

        return inner

    def within(self, name: str, start: float, end: float) -> List[Span]:
        return [s for s in self.items if s.name == name and s.start >= start and s.end <= end]
