#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads and warms up the cell named in ``BENCHMARK.json``, measures for
``--seconds``, checks the kept answers byte for byte against the
reference, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones, read from
a profiler trace of the window's first 5 s), ``device`` and, last, ``checks``: each
number compared with its limit.  The same checks are the last lines of
standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result.  JAX's compilation cache is kept in
``$JAX_COMPILATION_CACHE_DIR``, else in ``.jax_cache`` at the root of the
checkout.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]

NO_CHIP = 3


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from yardstick import runner, spec

    cell = spec.load_cell(args.workload)
    devices = runner.open_chips(cell)
    if devices is None:
        return NO_CHIP
    result = runner.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                             devices, STARTED)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
