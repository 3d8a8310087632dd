"""Decode step, by named scope: device ms per ``_decode_step`` program in the
operations of no scope: scan machinery, the embedding gather, the final
norm, and the copies and selects the compiler adds without scope metadata.
In the serving cells most of it is the price of two scopes' work: the
token write's (``kv_write``) whole-slice selects and the slice's layout
copies into its scatter loop, and attention's layout copies of the chunked
slice into its chunk loop (``PERF.md`` section 5).  Read from the traced
window's operations and the decode step's compiled text
(``chipbench/scopes.py``); none where the program has no scopes or the text
matches under 99% of the program's operation time."""

from chipbench import scopes

UNIT = "ms"
SOURCE = "device_trace"
LAYER = "model ops (decode program)"
MOVES = "serve_tokens_per_s"
SCOPE = "unscoped"


def read(run):
    return scopes.decode_ms(run, SCOPE)
