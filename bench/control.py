#!/usr/bin/env python3
"""The control: the plain reference, in bfloat16, in the program's place.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \\
        [--workers 8]

The configuration states float32 for the scheduling arithmetic, so the
control computes it one precision below: every result of the reference's
scheduling loop rounded to bfloat16 (``reference.bf16``) at every alpha
of the grid, the alpha chosen by the bfloat16 makespans, for each graph
a run of the cell with that seed serves.  Each control plan and its
sweep are held to the float64 reference by the comparison that decides
``correct``.  Prints each seed's numbers, then the smallest of each over
the seeds: the upper readings that the limits must stay below.

Host arithmetic only: no JAX, no program.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import reference as R  # noqa: E402
from benchlib.checks import NUMBERS, Checker  # noqa: E402


def readings(cases, limits, workers: int) -> dict:
    """The checker's numbers for the control's plan of each
    ``(prepared graph, alpha grid)`` case."""
    preps = [pp for pp, _ in cases]
    jobs = [(k, a) for k, (_, grid) in enumerate(cases) for a in grid]
    low = R.walk_many(preps, jobs, rnd=R.bf16, workers=workers)
    ref = R.walk_many(preps, jobs, workers=workers)
    chk = Checker(limits)
    at = 0
    for pp, grid in cases:
        mine = low[at:at + len(grid)]
        ref_ms = [w[0] for w in ref[at:at + len(grid)]]
        at += len(grid)
        best = 0            # the policy's rule: strict improvement
        for a in range(1, len(grid)):
            if mine[a][0] < mine[best][0] - 1e-12:
                best = a
        ms, proc, start, finish = mine[best]
        chk.sweep(ref_ms, [w[0] for w in mine], ms, "control")
        chk.schedule(pp, grid[best], proc, start, finish, ms, "control")
    return dict(chk.worst)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--workers", type=int, default=os.cpu_count())
    args = ap.parse_args()
    import importlib
    import run

    found = run.load_cell(args.workload)
    cfg, tr = found["config"], found["traffic"]
    load = importlib.import_module("loads." + tr["load"])
    low = {k: float("inf") for k in NUMBERS}
    for seed in (int(s) for s in args.seeds.split(",")):
        cases = load.control_cases(cfg, tr, seed)
        got = readings(cases, cfg["limits"], args.workers)
        print(json.dumps({"seed": seed, "plans": len(cases), **got}),
              flush=True)
        low = {k: min(low[k], got[k]) for k in NUMBERS}
    print(json.dumps({"control_min": low, "limits": cfg["limits"]}))


if __name__ == "__main__":
    main()
