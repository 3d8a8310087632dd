"""Device idle between the decode steps of a traced serving run.

``Server._serve`` runs one decode program a step, then the argmax the host
samples from, then, for each slot whose request finished, a prefill and a
slot insert.  Two consecutive decode programs with no prefill or insert
program between them are therefore one step that made no refill: the idle
time between them is what the host spends sampling, bookkeeping and
launching the next step.  Read from the device's programs alone, so it needs
neither the program's spans nor a correction for the host's clock.
"""

from __future__ import annotations

from typing import List, Tuple

import numpy as np

from chipbench.scopes import DECODE_PROGRAM

#: programs that run only where a step refills a slot
REFILL_PROGRAMS = ("_prefill_fn", "_insert_slot")

Interval = Tuple[int, int]


def idle_pieces(busy: np.ndarray, start: int, end: int) -> List[Interval]:
    """The pieces of ``[start, end)`` that no merged ``(start, end)`` row of
    ``busy`` covers."""
    out, t = [], start
    for s, e in busy[np.searchsorted(busy[:, 1], start, side="right"):]:
        if s >= end:
            break
        if s > t:
            out.append((t, int(s)))
        t = max(t, int(e))
    if end > t:
        out.append((t, end))
    return out


def refill_free_gaps(trace) -> List[List[Interval]]:
    """The idle pieces between each pair of consecutive decode programs of
    the first device that lie inside the window with no refill program
    between them."""
    if not trace.devices:
        return []
    device = trace.devices[0]
    lo, hi = trace.window
    busy = trace.busy_intervals(device)
    out = []
    last_end = None  # end of the last decode program, None after a refill
    for name, start, end in sorted(device.programs, key=lambda p: p[1]):
        if any(p in name for p in REFILL_PROGRAMS):
            last_end = None
        elif DECODE_PROGRAM in name:
            if last_end is not None and end <= hi:
                out.append(idle_pieces(busy, last_end, start))
            last_end = end if lo <= start else None
    return out


def decode_gap_ms(trace) -> List[float]:
    """Device-idle ms of each gap of :func:`refill_free_gaps`."""
    return [sum(e - s for s, e in pieces) * 1e-6 for pieces in refill_free_gaps(trace)]
