"""Decode step, by named scope: device ms per ``_decode_step`` program in the
operations of ``attention``, attention over the cache and the output
projection.  Not in it: the layout copies of the chunked cache slice into
the chunk loop, which the compiler adds without scope metadata; they read in
``decode_ms.unscoped`` (``PERF.md`` section 5).  Read from the traced
window's operations and the decode step's compiled text
(``chipbench/scopes.py``); none where the program has no scopes or the text
matches under 99% of the program's operation time."""

from chipbench import scopes

UNIT = "ms"
SOURCE = "device_trace"
LAYER = "model ops (decode program)"
MOVES = "serve_tokens_per_s"
SCOPE = "attention"


def read(run):
    return scopes.decode_ms(run, SCOPE)
