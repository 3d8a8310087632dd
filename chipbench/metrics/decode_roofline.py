"""Decode step: the least time the chip could take for the window's decode
work over the decode program's device time in the trace.  The least time
is max(operations / peak, bytes / bandwidth) of all decode steps together;
every step is bound by bytes at these sizes, so that equals the sum of the
steps' own bounds.  Bytes are the weights once a step plus each request's
cache up to its real position, not ``max_len``."""

from chipbench import flops

UNIT = "%"
SOURCE = "device_trace"
LAYER = "step functions (Server._prefill, Server._decode)"
MOVES = "serve_tokens_per_s"
PROGRAM = "_decode_step"


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace.program_s(PROGRAM)
    steps = len(run.spans_in_window("decode"))
    if not device_s or not steps:
        return None
    ops, moved = flops.decode_window(run.dims, run.requests, steps)
    least = max(ops / run.peaks["bf16_flops_per_s"], moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / device_s
