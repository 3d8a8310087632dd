"""Read the numbers a cell's limits are set from: the program's and the control's.

    python3 chipbench/control.py --workload stablelm_3b.decode_heavy --seeds 1-12

For each seed, in one process, this runs the cell through the harness as a
benchmark run does, with one call of the mix for a window.  The control,
the plain reference computed in float8
(:func:`chipbench.serve_check.control_gaps`), takes the program's place in
the comparison, so each seed's line shows the harness's verdict on it
(``correct`` should read false) beside the program's own numbers on the
same sample.  The benchmark's own runs never compute the control.  Per
number, the lower reading is the largest the program gives over the
seeds, the upper the smallest the control gives; each limit goes between
them (``chipbench/checks/<cell>.json``).
"""

import os
import pathlib
import sys
import time

# libtpu would otherwise log to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _seeds(text: str):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    import argparse
    import json

    from chipbench.bench import run_cell

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True, help="e.g. 1-12 or 5,9,20")
    args = ap.parse_args(argv)
    program, control = [], []
    for seed in args.seeds:
        line = run_cell(args.workload, seed, 0.0, False, t_process=time.perf_counter(),
                        control=True)
        numbers = line["program_checks"]
        program.append(numbers)
        control.append({n: line["checks"][n]["value"] for n in numbers})
        print(json.dumps({"seed": seed, "control_correct": line["correct"],
                          "program": numbers, "control": control[-1],
                          "limits": {n: line["checks"][n]["limit"] for n in numbers},
                          "checked_tokens": line["checked_tokens"]}), flush=True)
    summary = {}
    for n in program[0]:
        lower = max(p[n] for p in program)
        upper = min(c[n] for c in control)
        summary[n] = {"lower": lower, "upper": upper, "ratio": upper / max(lower, 1e-12)}
    print(json.dumps({"workload": args.workload, "seeds": len(program), **summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
