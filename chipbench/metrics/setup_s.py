"""Set-up: from process start to the start of the measured window (loading,
weights, warm-up and, in a run that compiles, compilation)."""

UNIT = "s"
SOURCE = "host_clock"


def read(run):
    return run.setup_s
