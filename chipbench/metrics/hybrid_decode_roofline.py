"""Decode step of a Zamba2 cell: the least time the chip could take for the
window's decode work over the decode program's device time in the trace.
The least time is max(operations / peak, bytes / bandwidth) of all decode
steps together (``chipbench/flops_zamba2.py``): the weights once a step,
each request's keys and values up to its real position in every
application of a shared block, and its SSM and conv states read and
written in every layer."""

from chipbench import flops_zamba2

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
    ops, moved = flops_zamba2.decode_window(run.dims, run.requests, steps)
    least = max(ops / run.peaks["bf16_flops_per_s"], moved / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / device_s
