"""95th percentile of the time between the completions of consecutive decode
steps, over every decode step in the window.  Every request active across
a gap sees it between two of its tokens; refill prefills between two steps
fall in the gap."""

import numpy as np

UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    ends = sorted(s.end for s in run.spans_in_window("decode"))
    if len(ends) < 2:
        return None
    return float(np.percentile(np.diff(ends), 95)) * 1e3
