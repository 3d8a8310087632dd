"""Operations and bytes that the served and trained work needs, from shapes.

The arithmetic follows ``benchmarks/roofline.py:model_flops_cell``: a
matmul of an ``n``-token activation against a ``d_in x d_out`` weight is
``2 n d_in d_out`` operations, attention is ``2 * 2 * Hq * Dh`` per
(query, key) pair (scores and values), and a causal query at position
``p`` sees ``p + 1`` keys.  Here the lengths are the real ones: decode
reads a request's cache up to its own position, not the cache's
``max_len``, and prefill counts the causal half.  The counts say what the
work needs, whatever program does it; a program that does more (masked
positions, recomputation) does not raise them.

Bytes are those the work must move at least once: every weight it uses
(the embedding only for the rows it gathers), the cache positions it reads
and the cache entries it writes, at the served precision.  Activations are
left out; at these sizes they are a small share.

Everything takes :class:`chipbench.reference.Dims`.
"""

from __future__ import annotations

from typing import Iterable


def block_params(m) -> int:
    """Matmul parameters of all transformer blocks (norm scales left out)."""
    qkv = m.d_model * m.head_dim * (m.heads + 2 * m.kv_heads)
    out = m.heads * m.head_dim * m.d_model
    mlp = 3 * m.d_model * m.d_ff
    return m.layers * (qkv + out + mlp)


def head_params(m) -> int:
    return m.d_model * m.vocab


def kv_bytes_per_position(m, dtype_bytes: int = 2) -> int:
    """Keys and values of one position in every layer."""
    return 2 * m.layers * m.kv_heads * m.head_dim * dtype_bytes


def attention_flops(m, first: int, n: int) -> float:
    """Causal attention for queries at positions ``first .. first + n - 1``."""
    keys_seen = n * first + n * (n + 1) // 2
    return 4.0 * m.heads * m.head_dim * m.layers * keys_seen


def weight_bytes(m, tokens: int, dtype_bytes: int = 2) -> float:
    """Weights one pass over ``tokens`` tokens reads: every block, the norms,
    the output head, and the embedding rows it gathers."""
    norms = m.layers * 2 * m.d_model + m.d_model
    return dtype_bytes * (block_params(m) + head_params(m) + norms + tokens * m.d_model)


def prefill_flops(m, prompt_len: int) -> float:
    """One request's prefill: every prompt token through the blocks, one
    row of logits (the program reads the last position only)."""
    return (2.0 * block_params(m) * prompt_len + attention_flops(m, 0, prompt_len)
            + 2.0 * head_params(m))


def prefill_bytes(m, prompt_len: int, dtype_bytes: int = 2) -> float:
    return (weight_bytes(m, prompt_len, dtype_bytes)
            + prompt_len * kv_bytes_per_position(m, dtype_bytes))


def decode_flops(m, positions: Iterable[int]) -> float:
    """One decode step; ``positions`` holds each active request's input
    position (its cache then holds ``p + 1`` entries)."""
    positions = list(positions)
    per_token = 2.0 * (block_params(m) + head_params(m))
    return sum(per_token + attention_flops(m, p, 1) for p in positions)


def decode_bytes(m, positions: Iterable[int], dtype_bytes: int = 2) -> float:
    positions = list(positions)
    kv = kv_bytes_per_position(m, dtype_bytes)
    return (weight_bytes(m, len(positions), dtype_bytes)
            + sum((p + 1) * kv for p in positions))


def decode_window(m, requests, steps: int, dtype_bytes: int = 2):
    """(operations, bytes) of ``steps`` decode steps that served
    ``requests``, each a (prompt length, output tokens) pair.  The weights
    are read once a step; each request's cache up to its own position."""
    positions = [p for prompt, n_out in requests
                 for p in request_decode_positions(prompt, n_out)]
    kv = sum(p + 1 for p in positions) * kv_bytes_per_position(m, dtype_bytes)
    rows = len(positions) * m.d_model * dtype_bytes
    return (decode_flops(m, positions),
            steps * weight_bytes(m, 0, dtype_bytes) + kv + rows)


def request_decode_positions(prompt_len: int, n_out: int) -> range:
    """Input positions of a request's decode steps: its first output token
    comes from prefill, each later one from a step at the previous token."""
    return range(prompt_len, prompt_len + n_out - 1)


def model_flops_served(m, prompt_len: int, n_out: int) -> float:
    """Model operations of one served request: ``2N`` per prompt and output
    token through the blocks, causal attention at real lengths, and the
    output head for each emitted token."""
    tokens = prompt_len + n_out - 1
    return (2.0 * block_params(m) * tokens + attention_flops(m, 0, tokens)
            + 2.0 * head_params(m) * n_out)

