"""Names of the ``jax.named_scope`` regions the model steps are split into.

Each name lands in the ``op_name`` metadata of every HLO instruction traced
inside its region, so a device trace's operations can be summed per region
(``chipbench/scopes.py``).  Scopes are metadata only: the compiled code is
the same with or without them.  The regions do not nest; an instruction in
none of them is scan machinery, an embedding gather, the final norm, or a
copy the compiler inserted.
"""

#: pre-attention norm, the q/k/v projections and rotary embedding
QKV = "qkv"
#: the tokens' k and v written into the cache: in decode, every layer's new
#: rows written into the stacked cache after the layer scan, in place; in
#: prefill, the prompt written into one layer's cache slice
KV_WRITE = "kv_write"
#: one layer's cache slice taken from the stacked cache: in decode, read in
#: place by attention; in prefill, read out of and written back into the
#: stacked cache the layer scan carries
KV_CARRY = "kv_carry"
#: attention over the cache and the output projection
ATTENTION = "attention"
#: post-attention norm and the feed-forward block, dense or MoE
MLP = "mlp"
#: the vocabulary projection of the last hidden state
LM_HEAD = "lm_head"
#: the Mamba2 state-space scan, chunked (prefill) or one step (decode), and
#: in the hybrid's decode the layer's state read and written in place
SSD = "ssd"
#: the rest of a Mamba2 layer: its norm, in_proj, conv (with the conv state),
#: gated norm and out_proj
MAMBA = "mamba"

ALL = (QKV, KV_WRITE, KV_CARRY, ATTENTION, MLP, LM_HEAD, SSD, MAMBA)
