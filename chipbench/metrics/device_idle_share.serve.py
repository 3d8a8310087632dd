"""Device: the share of the traced window in which no operation ran on the
chip (one minus the union of the operation intervals)."""

UNIT = "%"
SOURCE = "device_trace"
LAYER = "device"
MOVES = "serve_tokens_per_s"


def read(run):
    if run.trace is None:
        return None
    busy = run.trace.busy_s()
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / run.trace.window_s)
