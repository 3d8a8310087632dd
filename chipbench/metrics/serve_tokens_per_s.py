"""Output tokens of every request completed in the window, over the window's
wall time (first call's start to last call's end)."""

UNIT = "tokens/s"
SOURCE = "host_clock"


def read(run):
    return sum(n_out for _, n_out in run.requests) / run.window_s
