"""Benchmark driver: one function per paper table/figure + framework
benches.  Prints ``name,us_per_call,derived`` CSV.

  PYTHONPATH=src python -m benchmarks.run [--only fig6 fig7 ...]
                                          [--trace out/bench_trace.json]

JAX's persistent compilation cache lives in ``$JAX_COMPILATION_CACHE_DIR``
when it is set, else in ``.jax_cache`` at the repository root.

With ``--trace PATH`` the whole run executes under a `repro.obs` tracer:
every suite gets a span, the simulator/kernel instrumentation fires, and
two artifacts are persisted next to the CSV output — ``PATH`` (Chrome
trace_event JSON for chrome://tracing) and ``PATH`` with a
``.summary.json`` suffix (aggregated spans + counters + gauges).
"""
from __future__ import annotations

import argparse
import contextlib
import os
import sys
import traceback

from repro import obs
from repro.compile_cache import use_compile_cache

from . import kernels_bench, paper_tables, roofline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SUITES = {
    "fig3": paper_tables.fig3_bandwidth,
    "tables12": paper_tables.tables12_mttdl,
    "table3": paper_tables.table3_breakdown,
    "fig6": paper_tables.fig6_recovery,
    "fig7": paper_tables.fig7_degraded_read,
    "fig8": paper_tables.fig8_strip_block,
    "kernels": kernels_bench.gf_matmul_bench,
    "flash": kernels_bench.flash_attention_bench,
    "plans": kernels_bench.repair_plan_bench,
    "checkpoint": kernels_bench.checkpoint_bench,
    "roofline": roofline.roofline_rows,
    "repair_hlo": roofline.repair_collectives,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="+", default=None, choices=list(SUITES))
    ap.add_argument(
        "--trace", metavar="PATH", default=None,
        help="run under a repro.obs tracer; write Chrome trace JSON to PATH "
             "and an aggregated summary to PATH's .summary.json sibling",
    )
    args = ap.parse_args(argv)
    use_compile_cache(os.path.join(REPO, ".jax_cache"))
    names = args.only or list(SUITES)
    print("name,us_per_call,derived")
    failed = []
    ctx = obs.tracing("benchmarks") if args.trace else contextlib.nullcontext()
    with ctx as tracer:
        for name in names:
            try:
                with obs.span(f"suite.{name}", cat="bench"):
                    for row, us, derived in SUITES[name]():
                        print(f"{row},{us:.1f},{derived}")
            except Exception:  # keep the suite running; report at the end
                failed.append(name)
                traceback.print_exc(file=sys.stderr)
    if args.trace:
        d = os.path.dirname(args.trace)
        if d:
            os.makedirs(d, exist_ok=True)
        obs.write_chrome_trace(tracer, args.trace)
        stem, _ = os.path.splitext(args.trace)
        obs.write_summary(tracer, stem + ".summary.json")
        print(f"# trace: {args.trace}  summary: {stem}.summary.json",
              file=sys.stderr)
    if failed:
        print(f"# FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
