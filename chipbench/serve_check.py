"""Served tokens against the plain reference: the comparison behind ``correct``.

For each sampled request the reference runs once over its prompt and the
tokens the server emitted (teacher-forced), and reads, at every position
that produced a served token, how far that token's reference logit lies
below the reference's best.  Sampling is greedy, so a sound server emits
the argmax of logits that differ from the reference's by rounding only;
the widest such gap over the sample, and the mean gap, are the numbers
compared.

The control (:func:`control_gaps`) reads the same gap for the token that
the reference computed in float8 puts first at each position.
"""

from __future__ import annotations

import functools
from typing import Iterator, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference import Dims, logits_at


class Served(NamedTuple):
    prompt: np.ndarray   # (P,) int32
    tokens: np.ndarray   # (n,) int32, what the server emitted


def pick(served: Sequence[Served], n: int, seed: int) -> List[int]:
    """A block of ``n`` consecutive requests, at a position drawn from the
    seed, and the longest request if the block has none as long.

    Consecutive requests went into consecutive slots, so a block as long as
    the batch holds one request of every slot; every group of a mix holds
    every prompt length.
    """
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64, 0xC4EC]))
    starts = list(range(0, max(1, len(served) - n + 1), n))
    lo = int(starts[rng.integers(len(starts))])
    chosen = list(range(lo, min(lo + n, len(served))))
    size = lambda i: len(served[i].prompt) + len(served[i].tokens)
    longest = max(range(len(served)), key=size)
    if size(longest) > max(size(i) for i in chosen):
        chosen.append(longest)
    return chosen


def padded_length(sequence: int) -> int:
    """A row's reference length: what it feeds (prompt and all served tokens
    but the last), rounded up to 128."""
    return -(-(sequence - 1) // 128) * 128


def blocks(served: Sequence[Served], rows: int
           ) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray, int]]:
    """(tokens, at, emitted, real rows) blocks of ``rows`` requests, every
    row padded to the longest one's length, so one program serves them
    all; a short block repeats its first row to keep its shape."""
    n_out = len(served[0].tokens)
    length = max(padded_length(len(s.prompt) + n_out) for s in served)
    for lo in range(0, len(served), rows):
        group = list(served[lo:lo + rows])
        real = len(group)
        group = group + [group[0]] * (rows - real)
        toks = np.zeros((rows, length), np.int32)
        at = np.zeros((rows, n_out), np.int32)
        out = np.zeros((rows, n_out), np.int32)
        for i, s in enumerate(group):
            seq = np.concatenate([s.prompt, s.tokens[:-1]])
            toks[i, :len(seq)] = seq
            at[i] = len(s.prompt) - 1 + np.arange(n_out)
            out[i] = s.tokens
        yield toks, at, out, real


@functools.partial(jax.jit, static_argnums=(0,))
def served_gaps(m: Dims, w, tokens, at, emitted):
    logits = logits_at(m, w, tokens, at)
    got = jnp.take_along_axis(logits, emitted[..., None], axis=-1)[..., 0]
    return logits.max(-1) - got, jnp.isfinite(logits).all()


@functools.partial(jax.jit, static_argnums=(0,))
def control_gaps(m: Dims, w, tokens, at, emitted):
    del emitted  # the control's own first choice is read instead
    ref = logits_at(m, w, tokens, at)
    first = jnp.argmax(logits_at(m, w, tokens, at, "fp8"), axis=-1)
    got = jnp.take_along_axis(ref, first[..., None], axis=-1)[..., 0]
    return ref.max(-1) - got, jnp.isfinite(ref).all()


def gaps(fn, m: Dims, w, served: Sequence[Served], rows: int) -> Tuple[np.ndarray, bool]:
    """The gap at every served position, and whether every reference logit
    was finite."""
    out, finite = [], True
    for toks, at, emitted, real in blocks(served, rows):
        g, ok = fn(m, w, toks, at, emitted)
        out.append(np.asarray(g)[:real].ravel())
        finite = finite and bool(ok)
    return np.concatenate(out), finite


def numbers(g: np.ndarray) -> dict:
    """The numbers compared: the widest gap and the mean gap."""
    return {"logit_gap": float(g.max()), "mean_logit_gap": float(g.mean())}
