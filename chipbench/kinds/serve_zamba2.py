"""Serving cells of the Zamba2 configurations: ``Server.serve`` driven in
closed offline batches, as :mod:`chipbench.kinds.serve` drives the decoder
configurations, checked against :mod:`chipbench.reference_zamba2`.

Set-up makes the weights on the device in one jitted call from the seed,
builds one ``Server`` and warms up every program the window runs: a
prefill for each prompt length of the mix, the slot insert, the decode
step (also queued ahead of the last), sampling, the empty batch.  The
window is made of whole ``serve(list)`` calls, each one call of the mix,
until ``seconds`` have passed since the first began.  The benchmark's spans
wrap the instance's ``_prefill`` and ``_insert``; a prefill span ends once
the returned logits are ready.  Greedy and without EOS, the Server queues
each decode step before it waits for the last one's tokens, so a decode
span is that wait: it starts where the Server samples a decode step's
logits and ends with the tokens on the host.

After the window the peak memory is read and the program's state freed;
then the plain reference checks a sample of the requests the window
finished (:func:`chipbench.serve_check.gaps` with this reference's
``served_gaps``).

:func:`decode_split` splits a traced run's decode programs by the model's
named scopes for the ``hybrid_decode_ms.*`` and ``ssd_decode_roofline``
readers, compiling this kind's decode step again for the text.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
import weakref
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import scopes, serve_check, traffic
from chipbench.bench import BenchError, Outcome, Run, seed32
from chipbench.kinds.serve import _free, _peak_bytes
from chipbench.reference_zamba2 import Dims, control_gaps, make_weights, served_gaps
from chipbench.spans import Spans
from chipbench.trace import load as load_trace

#: what the program's ``ModelConfig`` needs to run the Zamba2 layer
_PROGRAM_FIELDS = {"hybrid_layer_ids", "num_mem_blocks", "adapter_rank", "ssm_groups"}


def program_config(conf):
    """The program's ``ModelConfig`` with every size taken from the file."""
    from repro.models import ModelConfig

    missing = _PROGRAM_FIELDS - {f.name for f in dataclasses.fields(ModelConfig)}
    if missing:
        raise BenchError(f"the program has no Zamba2 layer: ModelConfig lacks {sorted(missing)}")
    from repro.models.hybrid import NORM_EPS

    norm = (conf["program_layer"]["norm"], float(conf["rms_norm_eps"]))
    if norm != ("rmsnorm_unit_offset", NORM_EPS):
        raise BenchError(f"the program's norm is RMSNorm (unit offset, eps {NORM_EPS}); the "
                         f"file states {norm}")
    if not conf.get("tie_word_embeddings", False):
        raise BenchError("the program's Zamba2 ties its output head to the embedding")
    dims = Dims.from_config(conf)
    from repro.configs import get_config

    return dataclasses.replace(
        get_config(conf["program"]["config"]),
        n_layers=dims.layers,
        d_model=dims.d_model,
        n_heads=dims.heads,
        n_kv_heads=dims.kv_heads,
        head_dim=dims.head_dim,
        d_ff=dims.d_ff,
        vocab=dims.vocab,
        rope_theta=dims.rope_theta,
        tie_embeddings=True,
        ssm_state=dims.ssm_state,
        ssm_heads=dims.ssm_heads,
        ssm_head_dim=dims.ssm_head_dim,
        ssm_chunk=int(conf["chunk_size"]),
        ssm_groups=dims.ssm_groups,
        hybrid_layer_ids=dims.hybrid_layer_ids,
        num_mem_blocks=dims.mem_blocks,
        adapter_rank=dims.adapter_rank,
        dtype=jnp.bfloat16,
    )


def run(cell, *, seed, seconds, trace, trace_dir, t_process, devices, peaks, clock, fault,
        control=False):
    model_cfg = program_config(cell.config)
    from repro.models import Model
    from repro.runtime.serving import Request, ServeConfig, Server

    conf, mix, checks = cell.config, cell.traffic, cell.checks
    dims = Dims.from_config(conf)
    opts = conf["serve"]
    if traffic.max_sequence(mix) > int(opts["max_len"]):
        raise BenchError(f"{cell.name}: the mix needs {traffic.max_sequence(mix)} "
                         f"positions, max_len is {opts['max_len']}")
    key = jax.random.PRNGKey(seed32(seed))

    t_init = time.perf_counter()
    params = jax.jit(lambda k: Model(model_cfg).init(k)[0])(key)
    jax.block_until_ready(params)
    t_warm = time.perf_counter()

    serve_cfg = ServeConfig(
        batch_slots=int(opts["slots"]), max_len=int(opts["max_len"]),
        max_new_tokens=int(mix["new_tokens"]), eos=int(opts["eos"]),
        temperature=0.0, seed=seed32(seed),
    )
    server = Server(model_cfg, serve_cfg, params)
    spans = Spans()
    server._prefill = spans.wrap("prefill", server._prefill, ready=lambda out: out[0],
                                 size=lambda p, tokens: tokens.shape[1])
    server._insert = spans.wrap("insert", server._insert)
    decoded = set()  # ids of decode steps' logits not sampled yet
    decode, sample = server._decode, server._sample

    def decode_(params, tokens, state):
        logits, state = decode(params, tokens, state)
        decoded.add(id(logits))
        return logits, state

    def sample_(logits, rng):
        if id(logits) not in decoded:
            return sample(logits, rng)
        decoded.remove(id(logits))
        with spans.span("decode"):
            return sample(logits, rng)

    server._decode, server._sample = decode_, sample_
    if fault is not None:
        fault(server)

    # one request of each prompt length, three new tokens: every program and
    # every shape of the window, and no other; the first decode step queues
    # the second ahead
    server.cfg = dataclasses.replace(serve_cfg, max_new_tokens=3)
    warm = [Request(uid=i, prompt=np.ones((n,), np.int32))
            for i, n in enumerate(sorted(mix["prompt_lengths"]))]
    server.serve(warm)
    server.cfg = serve_cfg
    spans.items.clear()
    compile_setup_s = clock.seconds
    compile_events = clock.events

    trace_root = None
    if trace:
        trace_root = trace_dir or tempfile.mkdtemp(prefix="chipbench-trace-")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(str(trace_root), profiler_options=options)

    sent: List = []
    done: List = []
    with spans.span("window"):
        t0 = time.perf_counter()
        index = uid = 0
        while True:
            prompts = traffic.call(mix, dims.vocab, seed, index)
            reqs = [Request(uid=uid + i, prompt=p) for i, p in enumerate(prompts)]
            uid += len(reqs)
            index += 1
            with spans.span("call"):
                out = server.serve(reqs)
            sent += reqs
            done += out
            if time.perf_counter() - t0 >= seconds:
                break
        t1 = time.perf_counter()
    compiles_in_window = clock.events - compile_events

    tr = None
    if trace:
        jax.profiler.stop_trace()
        tr = load_trace(trace_root)
        if trace_dir is None:
            shutil.rmtree(trace_root, ignore_errors=True)

    peak = _peak_bytes(devices)
    _free(params)
    server.params = params = None
    del server
    gc.collect()

    # -- correct: every request finished in full, and a sample of them agrees
    # with the reference
    prompts = {r.uid: r.prompt for r in sent}
    n_out = int(mix["new_tokens"])
    whole = [c for c in done if c.uid in prompts and len(c.tokens) == n_out
             and all(0 <= t < dims.vocab for t in c.tokens)]
    failed = len(sent) - len(whole)
    served = [serve_check.Served(prompts[c.uid], np.asarray(c.tokens, np.int32)) for c in whole]
    chosen = ([served[i] for i in serve_check.pick(served, int(checks["sample_requests"]), seed)]
              if served else [])
    t_ref = time.perf_counter()
    limits = {name: float(c["limit"]) for name, c in checks["limits"].items()}
    read = {name: float("inf") for name in limits}
    finite, program_read = False, None
    if chosen:
        weights = jax.jit(make_weights, static_argnums=0)(dims, key)
        rows = int(checks["rows_per_block"])
        gaps, finite = serve_check.gaps(served_gaps, dims, weights, chosen, rows)
        read = serve_check.numbers(gaps)
        if control:
            # the control takes the program's place in the comparison; the
            # program's own numbers are kept beside it
            program_read = read
            gaps, finite = serve_check.gaps(control_gaps, dims, weights, chosen, rows)
            read = serve_check.numbers(gaps)
        _free(weights)
    ref_s = time.perf_counter() - t_ref
    correct = failed == 0 and finite and all(read[n] <= limits[n] for n in limits)

    setup_s = t0 - t_process
    notes = {
        "setup_parts": {
            "start_s": t_init - t_process,
            "weights_s": t_warm - t_init,
            "warmup_s": t0 - t_warm,
            "compile_s": compile_setup_s,
        },
        "compiles_in_window": compiles_in_window,
        "reference_s": ref_s,
        "checked_tokens": len(chosen) * n_out,
    }
    if control:
        notes["program_checks"] = program_read
    print(f"[{cell.name}] setup {setup_s:.3f} s {notes['setup_parts']}; window {t1 - t0:.3f} s, "
          f"{len(done)} requests; compiles in window {compiles_in_window}; peak {peak}; "
          f"reference {ref_s:.2f} s over {len(chosen)} requests", file=sys.stderr)
    run_ = Run(
        cell=cell, dims=dims, peaks=peaks, chips=len(devices), setup_s=setup_s,
        window=(t0, t1), spans=spans,
        requests=[(len(prompts[c.uid]), len(c.tokens)) for c in done],
        trace=tr,
    )
    return Outcome(
        run=run_, correct=bool(correct), attempted=len(sent), failed=failed,
        checks={
            **{n: {"value": read[n], "limit": limits[n]} for n in limits},
            "reference_finite": {"value": int(finite), "limit": 1},
            "unfinished_requests": {"value": failed, "limit": 0},
        },
        memory_peak_bytes=peak, notes=notes,
    )


# -- the decode step by named scope ---------------------------------------------------

_last: list = [None, None]  # the run last split, held weakly, and its split


def decode_split(run):
    """:func:`chipbench.scopes.split` of a traced run's decode programs, by
    the compiled text of this kind's decode step at the cell's sizes; once a
    run.  None where the run has no trace or the program no scopes."""
    if _last[0] is not None and _last[0]() is run:
        return _last[1]
    result = None
    names = scopes.model_scope_names()
    if run.trace is not None and run.trace.devices and names is not None:
        opts = run.cell.config["serve"]
        text = scopes.decode_text(program_config(run.cell.config), int(opts["slots"]),
                                  int(opts["max_len"]))
        if any(f"/{n}/" in text for n in names):
            result = scopes.split(run.trace, scopes.DECODE_PROGRAM,
                                  scopes.instruction_scopes(text, names))
    _last[:] = [weakref.ref(run), result]
    return result


def decode_ms(run, scope: str):
    """Device ms per decode step in ``scope``; None as :func:`decode_split`."""
    found = decode_split(run)
    if found is None:
        return None
    seconds, steps = found
    return 1e3 * seconds.get(scope, 0.0) / steps
