"""Record a small profiler trace on the chip, laid out as a benchmark run's.

    python3 chipbench/record_trace.py --out <dir>

Two tiny jitted programs, ``prefill`` and ``decode``, run inside the
benchmark's own spans (``window``, ``call``, ``prefill``, ``decode``), with
a host pause of 20 ms inside each ``call`` and outside every program, so
the longest idle gaps fall in ``call``.  The ``.xplane.pb`` it writes under
``<dir>`` is the fixture ``tests/chipbench/fixtures/chip_trace/`` holds; the
reduction of it is printed, for the test's expected numbers.
"""

import os
import pathlib
import sys
import time

# libtpu would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

_ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(_ROOT) not in sys.path:
    sys.path.insert(0, str(_ROOT))


def main(argv=None) -> int:
    import argparse
    import json

    import jax
    import jax.numpy as jnp

    from chipbench import trace
    from chipbench.spans import Spans

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", type=pathlib.Path, required=True)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print(f"needs a TPU; JAX's first device is {jax.devices()[0].platform}", file=sys.stderr)
        return 2

    @jax.jit
    def prefill(w, x):
        return jnp.tanh(x @ w) @ w

    @jax.jit
    def decode(w, x):
        return jnp.tanh(x[:8] @ w)

    w = jnp.ones((1024, 1024), jnp.bfloat16) * 1e-3
    x = jnp.ones((512, 1024), jnp.bfloat16)
    spans = Spans()
    run_prefill = spans.wrap("prefill", prefill, ready=lambda out: out)
    run_decode = spans.wrap("decode", decode, ready=lambda out: out)
    run_prefill(w, x)
    run_decode(w, x)  # compile both before the trace

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(args.out), profiler_options=options)
    with spans.span("window"):
        for _ in range(3):
            with spans.span("call"):
                run_prefill(w, x)
                for _ in range(4):
                    run_decode(w, x)
                time.sleep(0.02)
    jax.profiler.stop_trace()

    tr = trace.load(args.out)
    path = trace.find_xplane(args.out)
    print(json.dumps({
        "file": str(path), "bytes": path.stat().st_size,
        "devices": [d.name for d in tr.devices], "window_s": tr.window_s,
        "busy_s": tr.busy_s(), "programs": tr.top_programs(), "gaps": tr.idle_gaps(),
        "spans": sorted({n for n, _, _ in tr.host_spans}),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
