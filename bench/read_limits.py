#!/usr/bin/env python3
"""Take the readings that a cell's limits are set from, on the chip.

    python3 bench/read_limits.py --workload <name> --seeds 1,2,... \
        --control-seeds 1,2,3 [--seconds 2]

In one process, for each of ``--seeds`` it runs the cell through the
program with a short window and prints its compared numbers; for each
of ``--control-seeds`` it runs the control in the program's place (the
plain decode with every coefficient reduced to GF(2)) and, on the first,
the plain decode in full GF(2^8).  One JSON line per run.  The program's
sound runs give each limit's lower reading and the control its upper
one.  The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, required=True)
    ap.add_argument("--control-seeds", type=_seeds, required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)

    from yardstick import control, runner, spec

    cell = spec.load_cell(args.workload)
    devices = runner.open_chips(cell)
    if devices is None:
        return 3
    used = devices[:cell.chips]
    runs = [("program", seed, None) for seed in args.seeds]
    for i, seed in enumerate(args.control_seeds):
        runs.append(("control_gf2", seed, True))
        if i == 0:
            runs.append(("plain_decode", seed, False))
    for who, seed, gf2 in runs:
        program = (None if gf2 is None else
                   control.PlainDecode(cell.deployment, used, gf2=gf2))
        res = runner.run_cell(cell, seed, args.seconds, False, devices,
                              time.perf_counter(), program=program)
        print(json.dumps({"who": who, "seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"], "failed": res["failed"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
