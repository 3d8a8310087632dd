"""Batched serving runtime: prefill + decode with continuous batching.

A small but real serving loop:

* fixed-size decode batch with **slot recycling** (continuous batching):
  when a sequence finishes (EOS or max tokens), its slot is refilled from
  the request queue with a fresh prefill — prefill writes into the shared
  KV cache at that slot;
* greedy or temperature sampling;
* the decode step is a single jitted function over the cache pytree — this
  is the ``serve_step`` the decode/long-context dry-run cells lower;
* with greedy sampling and no EOS the next decode step is queued on the
  device before the host waits for this one's tokens, so the host's
  bookkeeping overlaps the device's work.
"""

from __future__ import annotations

import dataclasses
import queue
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import Model, ModelConfig

Pytree = Any


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    batch_slots: int = 8
    max_len: int = 512
    max_new_tokens: int = 32
    eos: int = 0  # negative: no EOS token, a request ends at max_new_tokens
    temperature: float = 0.0
    seed: int = 0


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (prompt_len,) int32


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: List[int]
    latency_s: float


class Server:
    """Single-host reference server; the same step functions lower on the
    production mesh (see launch/dryrun.py serve cells)."""

    def __init__(self, model_cfg: ModelConfig, cfg: ServeConfig, params: Pytree):
        self.model = Model(model_cfg, attn_impl="chunked")
        self.cfg = cfg
        self.params = params
        # the batch state is donated to the steps that rewrite it: a cache
        # held twice (input and output) does not fit beside the weights of a
        # multi-billion-parameter model on one chip
        self._decode = jax.jit(self._decode_step, donate_argnums=(2,))
        self._insert = jax.jit(self._insert_slot, donate_argnums=(0,))
        self._prefill = jax.jit(self._prefill_fn)
        # (B, V) logits -> (B, 1) greedy tokens, the decode step's input
        self._greedy = jax.jit(lambda logits: jnp.argmax(logits, -1).astype(jnp.int32)[:, None])
        self._greedy_of = (None, None)  # the last logits and their greedy tokens

    # -- jitted steps -----------------------------------------------------------

    def _prefill_fn(self, params, tokens):
        h, state = self.model.prefill(params, {"tokens": tokens}, self.cfg.max_len)
        logits = self.model.logits(params, h[:, -1:])
        return logits[:, 0], state

    def _decode_step(self, params, tokens, state):
        h, new_state = self.model.decode_step(params, tokens, state)
        logits = self.model.logits(params, h[:, -1:])
        return logits[:, 0], new_state

    def _tokens(self, logits: jax.Array) -> jax.Array:
        """The greedy tokens of ``logits`` on the device, taken once per
        logits array.  The device runs programs in the order they are
        queued, so an argmax taken again after the next step is queued would
        wait for that step too."""
        if self._greedy_of[0] is not logits:
            self._greedy_of = (logits, self._greedy(logits))
        return self._greedy_of[1]

    def _sample(self, logits: jax.Array, rng: np.random.Generator) -> np.ndarray:
        if self.cfg.temperature <= 0.0:
            return np.asarray(self._tokens(logits))[:, 0]
        probs = np.asarray(jax.nn.softmax(logits / self.cfg.temperature, axis=-1))
        return np.array(
            [rng.choice(probs.shape[-1], p=probs[i]) for i in range(probs.shape[0])]
        )

    # -- the serving loop ----------------------------------------------------------

    def serve(self, requests: List[Request]) -> List[Completion]:
        with obs.span("serve", requests=len(requests)) as sp:
            done = self._serve(requests)
            sp.set(completions=len(done))
        return done

    def _serve(self, requests: List[Request]) -> List[Completion]:
        """The serving loop.  With :mod:`repro.obs` enabled it records, under
        the ``serve`` span: a ``serve.fill`` per request put into a slot
        (``uid``, ``slot``, ``prompt_len``), holding ``serve.prefill``,
        ``serve.sample`` and ``serve.insert``; and a ``serve.step`` per
        decode step (``step``, ``active`` slots, ``refills`` made after it),
        holding ``serve.decode``, ``serve.sample`` (where the host waits for
        the device's result) and the step's refills.  A step that queues the
        next one ahead holds that step's ``serve.decode`` instead of its own.
        Where the model's decode reads KV caches through the decode-attention
        kernel, a ``serve.step`` also holds ``kv_read_share``: the share of
        each cache entry's stored blocks that the kernel copies for the
        active slots' positions, whose sums are the counters
        ``serve.kv_blocks_read`` and ``serve.kv_blocks_stored``.

        Greedy and without EOS, a decode step's input is the last step's
        tokens, which stay on the device, and a request ends at its count
        of tokens, which the host knows before the step runs.  So where no
        request ends with this step, the next is queued before the host
        waits for this one's tokens: the device runs the steps back to back
        and the host's bookkeeping overlaps them."""
        cfg = self.cfg
        rng = np.random.default_rng(cfg.seed)
        pending = queue.SimpleQueue()
        for r in requests:
            pending.put(r)

        # state per slot
        slot_req: List[Optional[Request]] = [None] * cfg.batch_slots
        slot_tokens: List[List[int]] = [[] for _ in range(cfg.batch_slots)]
        slot_start: List[float] = [0.0] * cfg.batch_slots
        done: List[Completion] = []

        state = None
        next_tokens = np.zeros((cfg.batch_slots,), np.int32)

        def fill_slot(slot: int) -> bool:
            """Put the next pending request into ``slot``; False if none."""
            nonlocal state, next_tokens
            if pending.empty():
                slot_req[slot] = None
                return False
            req = pending.get()
            slot_req[slot] = req
            slot_tokens[slot] = []
            slot_start[slot] = time.perf_counter()
            prompt = req.prompt[None, :]  # (1, L)
            with obs.span("serve.fill", uid=req.uid, slot=slot, prompt_len=prompt.shape[1]):
                with obs.span("serve.prefill"):
                    logits, st = self._prefill(self.params, jnp.asarray(prompt))
                with obs.span("serve.sample"):
                    tok = int(self._sample(logits, rng)[0])
                if state is None:
                    # first fill: an empty batch, filled like every later slot.
                    # An eager jnp.repeat would stage a broadcast copy of each
                    # cache leaf beside its result.
                    state = self._tree_map_batch(
                        lambda x, ax: jnp.zeros(
                            x.shape[:ax] + (cfg.batch_slots,) + x.shape[ax + 1:], x.dtype
                        ),
                        st,
                    )
                with obs.span("serve.insert"):
                    state = self._insert(state, st, jnp.int32(slot))
            slot_tokens[slot].append(tok)
            next_tokens[slot] = tok
            return True

        for slot in range(cfg.batch_slots):
            fill_slot(slot)

        run_ahead = cfg.temperature <= 0.0 and cfg.eos < 0
        queued = None  # logits of a step queued during the last step
        step = 0
        while any(r is not None for r in slot_req):
            active = sum(r is not None for r in slot_req)
            with obs.span("serve.step", step=step, active=active) as step_span:
                if obs.enabled():
                    self._count_kv_reads(step_span, [
                        len(req.prompt) + len(slot_tokens[slot]) - 1
                        for slot, req in enumerate(slot_req) if req is not None])
                if queued is None:
                    with obs.span("serve.decode"):
                        logits, state = self._decode(
                            self.params, jnp.asarray(next_tokens[:, None]), state
                        )
                else:
                    logits, queued = queued, None
                if run_ahead and all(
                    req is None or len(slot_tokens[slot]) + 1 < cfg.max_new_tokens
                    for slot, req in enumerate(slot_req)
                ):
                    with obs.span("serve.decode"):
                        queued, state = self._decode(self.params, self._tokens(logits), state)
                with obs.span("serve.sample"):
                    sampled = self._sample(logits, rng)
                refills = 0
                for slot, req in enumerate(slot_req):
                    if req is None:
                        continue
                    tok = int(sampled[slot])
                    slot_tokens[slot].append(tok)
                    next_tokens[slot] = tok
                    if tok == cfg.eos or len(slot_tokens[slot]) >= cfg.max_new_tokens:
                        done.append(
                            Completion(
                                uid=req.uid,
                                tokens=list(slot_tokens[slot]),
                                latency_s=time.perf_counter() - slot_start[slot],
                            )
                        )
                        refills += fill_slot(slot)
                step_span.set(refills=refills)
            step += 1
        return sorted(done, key=lambda c: c.uid)

    def _count_kv_reads(self, step_span, positions: List[int]) -> None:
        """``kv_read_share`` on ``step_span`` and its counters, for a decode
        step of slots at ``positions``."""
        blocks = self.model.decode_kv_blocks(positions, self.cfg.max_len)
        if blocks is None:
            return
        read, stored = blocks
        step_span.set(kv_read_share=read / stored)
        obs.metrics().counter("serve.kv_blocks_read").inc(read)
        obs.metrics().counter("serve.kv_blocks_stored").inc(stored)

    # -- slot surgery -------------------------------------------------------------
    # State leaves keyed by their top-level name:
    #   kv:   (L|apps, B, S, H, Dh) -> batch axis 1
    #   ssm:  (L, B, H, P, N)       -> batch axis 1
    #   conv: (L, B, K, C)          -> batch axis 1
    #   pos:  (B,)                  -> batch axis 0
    #   enc:  (B, T, D)             -> batch axis 0
    _BATCH_AXIS = {"kv": 1, "ssm": 1, "conv": 1, "pos": 0, "enc": 0}

    @classmethod
    def _leaf_axis(cls, path) -> int:
        key = None
        for p in path:
            if hasattr(p, "key"):
                key = str(p.key)
                break
        return cls._BATCH_AXIS.get(key, 0)

    @classmethod
    def _tree_map_batch(cls, fn, *trees):
        """``fn(*leaves, batch_axis)`` over matching leaves of ``trees``."""
        return jax.tree_util.tree_map_with_path(
            lambda path, *xs: fn(*xs, cls._leaf_axis(path)), *trees
        )

    @classmethod
    def _insert_slot(cls, state: Pytree, one: Pytree, slot: jax.Array) -> Pytree:
        return cls._tree_map_batch(
            lambda full, o, ax: jax.lax.dynamic_update_slice_in_dim(full, o, slot, ax),
            state, one,
        )
