"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the checkout's root.  The cell, its configuration, traffic, limits
and metrics are found by name (see ``chipbench/bench.py``).  The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced,
``breakdown``; last in it, ``checks`` holds each number that decided
``correct`` beside its limit, and the same lines end standard error.  The
run exits non-zero and prints no result where JAX finds no TPU or fewer
chips than the cell asks for.
"""

import time

_T_PROCESS = time.perf_counter()

import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# libtpu would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench.bench import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_process=_T_PROCESS))
