"""The one traffic generator: reads a mix's data file, makes requests from the seed.

A serving mix (``"kind": "closed_batches"``) is a closed offline loop: the
benchmark hands the server one call of requests at a time and sends the
next call when the last has returned.  The file gives

* ``prompt_lengths``: how many prompts of each length make up one group;
* ``groups_per_call``: groups in one call;
* ``new_tokens``: output tokens of every request;
* ``why``: one line on what the mix is for.

Every group holds the same lengths, so every seed sends the same work;
the seed shuffles the order inside each group and draws the prompt tokens,
uniformly over ``[1, vocab)``.  Call ``k`` depends on ``(seed, k)`` alone.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict, List

import numpy as np


def load(path: pathlib.Path) -> Dict:
    mix = json.loads(pathlib.Path(path).read_text())
    if mix.get("kind") != "closed_batches":
        raise ValueError(f"{path}: unknown traffic kind {mix.get('kind')!r}")
    lengths = {int(k): int(v) for k, v in mix["prompt_lengths"].items()}
    if not lengths or min(lengths) < 1 or min(lengths.values()) < 1:
        raise ValueError(f"{path}: prompt_lengths needs positive lengths and counts")
    return dict(mix, prompt_lengths=lengths)


def group_lengths(mix: Dict) -> List[int]:
    return [n for n, count in sorted(mix["prompt_lengths"].items()) for _ in range(count)]


def max_sequence(mix: Dict) -> int:
    """Longest prompt plus its output: what a cache slot has to hold."""
    return max(mix["prompt_lengths"]) + int(mix["new_tokens"])


def call(mix: Dict, vocab: int, seed: int, index: int) -> List[np.ndarray]:
    """The prompts of call ``index`` in the order they are sent."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64, int(index)]))
    prompts = []
    for _ in range(int(mix["groups_per_call"])):
        for n in rng.permutation(group_lengths(mix)):
            prompts.append(rng.integers(1, vocab, size=int(n), dtype=np.int32))
    return prompts
