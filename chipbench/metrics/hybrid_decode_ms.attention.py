"""Decode step of a Zamba2 cell, by named scope: device ms per ``_decode_step``
program in ``attention``, each shared-block application's read of its KV
cache, the softmax and the output projection.  Read from the traced window's
operations and this kind's decode step compiled again for the text
(``chipbench/kinds/serve_zamba2.py:decode_split``); none where the program
has no scopes or the text matches under 99% of the program's operation time."""

from chipbench.kinds import serve_zamba2

UNIT = "ms"
SOURCE = "device_trace"
LAYER = "model ops (decode program)"
MOVES = "serve_tokens_per_s"
SCOPE = "attention"


def read(run):
    return serve_zamba2.decode_ms(run, SCOPE)
