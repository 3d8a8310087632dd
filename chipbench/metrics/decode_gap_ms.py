"""Scheduler: the median device-idle ms between consecutive ``_decode_step``
programs with no prefill or slot insert between them, that is between the
steps of ``Server._serve`` that refilled no slot (``chipbench/gaps.py``).
The host samples the step's tokens and launches the next step in it.  Its
split by the ``Server``'s spans is ``chipbench/timeline.py``'s."""

import statistics

from chipbench import gaps

UNIT = "ms"
SOURCE = "device_trace"
LAYER = "scheduler (Server._serve)"
MOVES = "serve_tokens_per_s"


def read(run):
    if run.trace is None:
        return None
    idle = gaps.decode_gap_ms(run.trace)
    return statistics.median(idle) if idle else None
