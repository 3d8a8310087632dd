"""Decode step of a Zamba2 cell, by named scope: device ms per ``_decode_step``
program in ``ssd``, the Mamba2 scan of every layer, with the layer's state
read and written in place.  Read from the traced window's operations and
this kind's decode step compiled again for the text
(``chipbench/kinds/serve_zamba2.py:decode_split``); none where the program
has no scopes or the text matches under 99% of the program's operation time."""

from chipbench.kinds import serve_zamba2

UNIT = "ms"
SOURCE = "device_trace"
LAYER = "model ops (decode program)"
MOVES = "serve_tokens_per_s"
SCOPE = "ssd"


def read(run):
    return serve_zamba2.decode_ms(run, SCOPE)
