"""Whole serve step: model operations of every prompt and output token of
the requests done in the traced window (2N per token through the blocks,
causal attention at real lengths, the output head per emitted token), over
the window times the chips' bf16 peak."""

from chipbench import flops

UNIT = "%"
SOURCE = "device_trace"
LAYER = "whole serve step"
MOVES = "serve_tokens_per_s"


def read(run):
    if run.trace is None or not run.requests:
        return None
    ops = sum(flops.model_flops_served(run.dims, p, n) for p, n in run.requests)
    return 100.0 * ops / (run.trace.window_s * run.chips * run.peaks["bf16_flops_per_s"])
