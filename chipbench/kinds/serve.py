"""Serving cells: ``Server.serve`` driven in closed offline batches.

Set-up makes the weights on the device in one jitted call from the seed,
builds one ``Server`` and warms up every program the window runs: a
prefill for each prompt length of the mix, the slot insert, the decode
step, sampling, the empty batch.  The window is made of whole
``serve(list)`` calls, each one call of the mix; calls go on until
``seconds`` have passed since the first began, and the window runs from the
first call's start to the last call's end.

The benchmark's own spans wrap the instance's ``_prefill``, ``_decode`` and
``_insert``.  The prefill and decode spans end once the returned logits
are ready, which ``Server._sample`` waits for straight after anyway.

After the window the peak memory is read and the program's state freed;
then the plain reference (:mod:`chipbench.serve_check`) checks a sample of
the requests the window finished.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import serve_check, traffic
from chipbench.bench import BenchError, Outcome, Run, seed32
from chipbench.reference import Dims, make_weights
from chipbench.spans import Spans
from chipbench.trace import load as load_trace


def program_config(conf):
    """The program's ``ModelConfig`` with every size taken from the file."""
    from repro.configs import get_config

    layer = conf["program_layer"]
    if (layer["norm"], float(layer["norm_eps"]), float(layer["rotary_fraction"])) != (
            "rmsnorm_unit_offset", 1e-6, 1.0):
        raise BenchError(f"the program's layer is RMSNorm (unit offset, eps 1e-6) with rotary "
                         f"over the whole head; the file states {layer}")
    return dataclasses.replace(
        get_config(conf["program"]["config"]),
        n_layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        head_dim=conf.get("head_dim"),
        d_ff=int(conf["intermediate_size"]),
        vocab=int(conf["vocab_size"]),
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf.get("tie_word_embeddings", False)),
        qkv_bias=bool(conf.get("use_qkv_bias", False)),
        dtype=jnp.bfloat16,
    )


def _peak_bytes(devices):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def _free(tree) -> None:
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array):
            x.delete()
    gc.collect()


def run(cell, *, seed, seconds, trace, trace_dir, t_process, devices, peaks, clock, fault,
        control=False):
    from repro.models import Model
    from repro.runtime.serving import Request, ServeConfig, Server

    conf, mix, checks = cell.config, cell.traffic, cell.checks
    dims = Dims.from_config(conf)
    model_cfg = program_config(conf)
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
    server._decode = spans.wrap("decode", server._decode, ready=lambda out: out[0])
    server._insert = spans.wrap("insert", server._insert)
    if fault is not None:
        fault(server)

    # one request of each prompt length, two new tokens: every program and
    # every shape of the window, and no other
    server.cfg = dataclasses.replace(serve_cfg, max_new_tokens=2)
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
        gaps, finite = serve_check.gaps(serve_check.served_gaps, dims, weights, chosen, rows)
        read = serve_check.numbers(gaps)
        if control:
            # the control takes the program's place in the comparison; the
            # program's own numbers are kept beside it
            program_read = read
            gaps, finite = serve_check.gaps(serve_check.control_gaps, dims, weights, chosen, rows)
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
