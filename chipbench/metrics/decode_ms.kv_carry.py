"""Decode step, by named scope: device ms per ``_decode_step`` program in the
operations of ``kv_carry``, one layer's cache slice read out of the stacked
cache the layer scan carries, and written back (``ck_all[i]``,
``.at[i].set``).  Read from the traced window's operations and the decode
step's compiled text (``chipbench/scopes.py``); none where the program has
no scopes or the text matches under 99% of the program's operation time."""

from chipbench import scopes

UNIT = "ms"
SOURCE = "device_trace"
LAYER = "model ops (decode program)"
MOVES = "serve_tokens_per_s"
SCOPE = "kv_carry"


def read(run):
    return scopes.decode_ms(run, SCOPE)
