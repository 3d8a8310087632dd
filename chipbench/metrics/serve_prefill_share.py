"""Scheduler: the share of the window the host spent inside ``_prefill``
and ``_insert`` calls (benchmark spans; a prefill span ends when its logits
are ready)."""

UNIT = "%"
SOURCE = "host_clock"
LAYER = "scheduler (Server._serve)"
MOVES = "serve_itl_p95_ms"


def read(run):
    spans = run.spans_in_window("prefill") + run.spans_in_window("insert")
    return 100.0 * sum(s.end - s.start for s in spans) / run.window_s
