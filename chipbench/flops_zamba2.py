"""Operations and bytes that a Zamba2 configuration's decode work needs, from shapes.

As :mod:`chipbench.flops` counts them for the decoder configurations: a
matmul of ``n`` tokens against a ``d_in x d_out`` weight is ``2 n d_in d_out``
operations, attention ``2 * 2 * Hq * Dh`` per (query, key) pair, and lengths
are the real ones, not the cache's ``max_len``.  The counts say what the
work needs, whatever program does it.

Bytes are those a decode step must move at least once: every weight once a
step (bf16; the SSM's ``A``, ``D`` and ``dt`` bias float32), a shared
block's once for each of its applications (they run layers apart, and a
block's 0.67 GB cannot wait on the chip in between), the embedding
rows it gathers, each request's keys and values up to its own position in
every application of a shared block, the rows it writes there, and each
request's SSM state (float32) and conv state (bf16) of every layer, read
and written.  Activations are left out.

Everything takes :class:`chipbench.reference_zamba2.Dims`.
"""

from __future__ import annotations

from typing import Iterable

from chipbench.flops import request_decode_positions

CONV = 4


def mamba_matmul_params(m) -> int:
    """in_proj and out_proj of one Mamba2 layer."""
    proj = 2 * m.d_inner + 2 * m.ssm_groups * m.ssm_state + m.ssm_heads
    return m.d_model * proj + m.d_inner * m.d_model


def block_matmul_params(m) -> int:
    """One shared block: q, k, v over [h ; e], the output projection, the
    gated MLP."""
    qkv = 2 * m.d_model * m.head_dim * (m.heads + 2 * m.kv_heads)
    return qkv + m.heads * m.head_dim * m.d_model + 3 * m.d_model * m.d_ff


def app_matmul_params(m) -> int:
    """One application's adapter and output linear."""
    return m.adapter_rank * (m.d_model + 2 * m.d_ff) + m.d_model * m.d_model


def weight_bytes(m) -> float:
    """The weights one decode step reads: the layers with their norms, conv
    and SSM parameters, a block's for each application, the applications'
    own, the tied embedding."""
    d = m.d_model
    mamba = (2 * (mamba_matmul_params(m) + (CONV + 1) * m.conv_dim + m.d_inner + d)
             + 4 * 3 * m.ssm_heads)
    block = 2 * (block_matmul_params(m) + 3 * d)
    n_apps = len(m.hybrid_layer_ids)
    return (m.layers * mamba + n_apps * block
            + 2 * (n_apps * app_matmul_params(m) + m.vocab * d + d))


def kv_bytes_per_position(m, dtype_bytes: int = 2) -> int:
    """Keys and values of one position in every application."""
    return 2 * len(m.hybrid_layer_ids) * m.kv_heads * m.head_dim * dtype_bytes


def state_bytes(m) -> int:
    """One request's SSM state (float32) and conv state (bf16), every layer."""
    ssm = m.ssm_heads * m.ssm_head_dim * m.ssm_state * 4
    return m.layers * (ssm + (CONV - 1) * m.conv_dim * 2)


def ssm_state_bytes(m) -> int:
    """One request's SSM state (float32), every layer."""
    return m.layers * m.ssm_heads * m.ssm_head_dim * m.ssm_state * 4


def decode_flops(m, positions: Iterable[int]) -> float:
    """One token per entry of ``positions`` (each request's input position):
    the projections, the conv, the recurrence (decay, input, output: 6
    operations per state element) and attention over ``p + 1`` keys."""
    n_apps = len(m.hybrid_layer_ids)
    matmul = (m.layers * mamba_matmul_params(m) + n_apps * (block_matmul_params(m)
              + app_matmul_params(m)) + m.vocab * m.d_model)
    scan = m.layers * (6 * m.ssm_heads * m.ssm_head_dim * m.ssm_state + 2 * CONV * m.conv_dim)
    per_key = 4.0 * n_apps * m.heads * m.head_dim
    return sum(2.0 * matmul + scan + per_key * (p + 1) for p in positions)


def decode_window(m, requests, steps: int):
    """(operations, bytes) of ``steps`` decode steps that served ``requests``,
    each a (prompt length, output tokens) pair."""
    positions = [p for prompt, n_out in requests
                 for p in request_decode_positions(prompt, n_out)]
    kv = kv_bytes_per_position(m)
    moved = (steps * weight_bytes(m)
             + sum(p + 1 for p in positions) * kv       # keys and values read
             + len(positions) * (kv + 2 * m.d_model)    # rows written, embedding rows
             + len(positions) * 2 * state_bytes(m))     # states read and written
    return decode_flops(m, positions), moved


def ssd_window_bytes(m, requests) -> float:
    """The SSM states the window's decode steps read and write."""
    tokens = sum(len(request_decode_positions(p, n)) for p, n in requests)
    return tokens * 2 * ssm_state_bytes(m)
