"""Decode step, by named scope: device ms per ``_decode_step`` program in the
operations of ``kv_write``, the current token's k and v written into the
layer's cache slice (the vmapped ``dynamic_update_slice``, compiled to a
scatter loop).  Not in it: the slice's layout copies into that loop and the
select of the whole slice after it, which the compiler adds without scope
metadata; they read in ``decode_ms.unscoped`` (``PERF.md`` section 5).
Read from the traced window's operations and the decode step's compiled
text (``chipbench/scopes.py``); none where the program has no scopes or the
text matches under 99% of the program's operation time."""

from chipbench import scopes

UNIT = "ms"
SOURCE = "device_trace"
LAYER = "model ops (decode program)"
MOVES = "serve_tokens_per_s"
SCOPE = "kv_write"


def read(run):
    return scopes.decode_ms(run, SCOPE)
