"""The Mamba2 scan in a Zamba2 cell's decode step: the least time of the
``ssd`` scope's work, each active request's SSM state read and written in
every layer at the chip's bandwidth (``chipbench/flops_zamba2.py``), over
the device time of the scope's operations in the window's decode programs
(``chipbench/kinds/serve_zamba2.py:decode_split``); none where the split
finds no match."""

from chipbench import flops_zamba2
from chipbench.kinds import serve_zamba2

UNIT = "%"
SOURCE = "device_trace"
LAYER = "model ops (decode program)"
MOVES = "serve_tokens_per_s"
SCOPE = "ssd"


def read(run):
    found = serve_zamba2.decode_split(run)
    if found is None or not found[0].get(SCOPE):
        return None
    least = flops_zamba2.ssd_window_bytes(run.dims, run.requests) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / found[0][SCOPE]
