# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

Sections:
  table1        occupancy before/after RegDem          (paper Table 1)
  fig6          variant speedups over nvcc             (paper Fig. 6)
  fig7          post-spilling optimization ablation    (paper Fig. 7)
  fig8          candidate-strategy comparison          (paper Fig. 8)
  fig9          predictor vs oracle vs naive           (paper Fig. 9)
  binary        pseudo-cubin codec throughput + sizes  (writes BENCH_binary.json)
  pipeline      batch-translate throughput, cache hit rate, per-pass breakdown
                (writes BENCH_pipeline.json)
  sim           simulator-engine throughput + sim-cache behaviour vs the
                recorded pre-optimization baseline     (writes BENCH_sim.json)
  arch          cross-architecture Table-3 demotion results + occupancy
                comparison over every registered arch  (writes BENCH_arch.json)
  search        predictor-guided autotuning search vs the fixed variant set
                over all 9 benchmarks x every arch    (writes BENCH_search.json)
  corpus        the real-workload Pallas corpus (repro.data.corpus) through
                the same anchored search, every arch  (writes BENCH_corpus.json)
  obs           telemetry overhead (enabled vs disabled) + span throughput
                (writes BENCH_obs.json)
  serve         translation-daemon latency, warm-restart hit rate, and the
                serving invariant under a fault storm (writes BENCH_serve.json)

Run all: ``PYTHONPATH=src python -m benchmarks.run``
Some sections: ``... -m benchmarks.run --only fig6,fig7`` (comma-separated
and/or repeated ``--only``); an unknown section name is an error.
``--trace out.json`` records telemetry for the whole harness run and writes
a Chrome trace (load in chrome://tracing or Perfetto); ``--trace out.jsonl``
writes the JSONL event log instead.
"""

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        action="append",
        default=None,
        metavar="SECTION[,SECTION...]",
        help="run only these sections (comma-separated, repeatable): "
             "table1|fig6|fig7|fig8|fig9|binary|"
             "pipeline|sim|arch|search|corpus|obs|serve",
    )
    ap.add_argument("--binary-json", default=None, metavar="PATH",
                    help="where the binary section writes its JSON report "
                         "(default: BENCH_binary.json in the cwd)")
    ap.add_argument("--pipeline-json", default=None, metavar="PATH",
                    help="where the pipeline section writes its JSON report "
                         "(default: BENCH_pipeline.json in the cwd)")
    ap.add_argument("--sim-json", default=None, metavar="PATH",
                    help="where the sim section writes its JSON report "
                         "(default: BENCH_sim.json in the cwd)")
    ap.add_argument("--arch-json", default=None, metavar="PATH",
                    help="where the arch section writes its JSON report "
                         "(default: BENCH_arch.json in the cwd)")
    ap.add_argument("--search-json", default=None, metavar="PATH",
                    help="where the search section writes its JSON report "
                         "(default: BENCH_search.json in the cwd)")
    ap.add_argument("--search-workers", type=int, default=0, metavar="N",
                    help="process-pool size for the search section "
                         "(default: in-process; results are identical)")
    ap.add_argument("--corpus-json", default=None, metavar="PATH",
                    help="where the corpus section writes its JSON report "
                         "(default: BENCH_corpus.json in the cwd)")
    ap.add_argument("--obs-json", default=None, metavar="PATH",
                    help="where the obs section writes its JSON report "
                         "(default: BENCH_obs.json in the cwd)")
    ap.add_argument("--serve-json", default=None, metavar="PATH",
                    help="where the serve section writes its JSON report "
                         "(default: BENCH_serve.json in the cwd)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record telemetry for the whole run and write a "
                         "Chrome trace (.json) or JSONL event log (.jsonl)")
    args = ap.parse_args()

    from benchmarks import (
        arch_bench,
        binary_bench,
        corpus_bench,
        obs_bench,
        paper_figs,
        pipeline_bench,
        search_bench,
        serve_bench,
        sim_bench,
    )

    def binary_rows():
        return binary_bench.binary_rows(args.binary_json or binary_bench.JSON_PATH)

    def pipeline_rows():
        return pipeline_bench.pipeline_rows(args.pipeline_json or pipeline_bench.JSON_PATH)

    def sim_rows():
        return sim_bench.sim_rows(args.sim_json or sim_bench.JSON_PATH)

    def arch_rows():
        return arch_bench.arch_rows(args.arch_json or arch_bench.JSON_PATH)

    def search_rows():
        return search_bench.search_rows(
            args.search_json or search_bench.JSON_PATH,
            workers=args.search_workers,
        )

    def corpus_rows():
        return corpus_bench.corpus_rows(
            args.corpus_json or corpus_bench.JSON_PATH,
            workers=args.search_workers,
        )

    def obs_rows():
        return obs_bench.obs_rows(args.obs_json or obs_bench.JSON_PATH)

    def serve_rows():
        return serve_bench.serve_rows(args.serve_json or serve_bench.JSON_PATH)

    sections = {
        "table1": paper_figs.table1_occupancy,
        "fig6": paper_figs.fig6_speedups,
        "fig7": paper_figs.fig7_postopt,
        "fig8": paper_figs.fig8_candidates,
        "fig9": paper_figs.fig9_predictor,
        "binary": binary_rows,
        "pipeline": pipeline_rows,
        "sim": sim_rows,
        "arch": arch_rows,
        "search": search_rows,
        "corpus": corpus_rows,
        "obs": obs_rows,
        "serve": serve_rows,
    }

    selected = None
    if args.only is not None:
        selected = []
        for chunk in args.only:
            selected.extend(s.strip() for s in chunk.split(",") if s.strip())
        unknown = sorted(set(selected) - set(sections))
        if unknown:
            ap.error(
                f"unknown --only section(s): {', '.join(unknown)} "
                f"(choose from: {', '.join(sections)})"
            )
        if not selected:
            # "--only ''" / "--only ," must not silently run zero sections
            ap.error(f"--only selected no sections (choose from: {', '.join(sections)})")

    if args.trace:
        from repro import obs

        obs.enable()

    print("name,us_per_call,derived")
    for name, fn in sections.items():
        if selected is not None and name not in selected:
            continue
        t0 = time.time()
        for row in fn():
            print(row)
        print(f"section_{name}_wall,{(time.time()-t0)*1e6:.0f},elapsed", file=sys.stderr)

    if args.trace:
        fmt = obs.write_trace(args.trace)
        spans = obs.get_telemetry().event_count()
        print(f"trace: {spans} spans -> {args.trace} ({fmt})", file=sys.stderr)


if __name__ == "__main__":
    main()
