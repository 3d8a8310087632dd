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
#: the current token's k and v written into one layer's cache slice
KV_WRITE = "kv_write"
#: one layer's cache slice read out of, and written back into, the stacked
#: cache the layer scan carries
KV_CARRY = "kv_carry"
#: attention over the cache and the output projection
ATTENTION = "attention"
#: post-attention norm and the feed-forward block, dense or MoE
MLP = "mlp"
#: the vocabulary projection of the last hidden state
LM_HEAD = "lm_head"
#: the Mamba2 state-space scan, chunked (prefill) or one step (decode)
SSD = "ssd"

ALL = (QKV, KV_WRITE, KV_CARRY, ATTENTION, MLP, LM_HEAD, SSD)
