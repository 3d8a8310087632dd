"""Prefill: the least time the chip could take for the window's prefills,
summed per call as max(operations / peak, bytes / bandwidth) at each real
prompt length (causal attention), over the prefill program's device time
in the trace."""

from chipbench import flops

UNIT = "%"
SOURCE = "device_trace"
LAYER = "step functions (Server._prefill, Server._decode)"
MOVES = "serve_tokens_per_s"
PROGRAM = "_prefill_fn"


def read(run):
    if run.trace is None:
        return None
    device_s = run.trace.program_s(PROGRAM)
    calls = run.spans_in_window("prefill")
    if not device_s or not calls:
        return None
    m, pk = run.dims, run.peaks
    least = sum(max(flops.prefill_flops(m, c.size) / pk["bf16_flops_per_s"],
                    flops.prefill_bytes(m, c.size) / pk["hbm_bytes_per_s"]) for c in calls)
    return 100.0 * least / device_s
