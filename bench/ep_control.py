#!/usr/bin/env python3
"""The control of the expert-parallel step cell: the plain reference,
in bfloat16, in the program's place.

    python3 bench/ep_control.py --seeds 11,12,13 [--workers 8]

The configuration states float32 for the scheduling arithmetic and holds
served plans to the reference computed in float32
(``check.precision``), so the control computes one precision below:
every result of the reference's scheduling loop rounded to bfloat16 at
each alpha the check samples before the window, the alpha chosen by the
bfloat16 makespans, for each graph of a run's pool.  Each control plan
and its sweep are held to the float32 reference by the comparison that
decides ``correct``.  Prints each seed's numbers, then the smallest of
each over the seeds: the upper readings that the limits must stay below.

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

from benchlib import ep_graph as EG  # noqa: E402
from benchlib import reference as R  # noqa: E402
from benchlib.checks import NUMBERS  # noqa: E402

WORKLOAD = "dbrx-ep16-mesh4x4-prefill.submit"


def readings(cases, limits, precision: str, workers: int) -> dict:
    """The checker's numbers for the bfloat16 plan of each
    ``(prepared graph, alphas)`` case against the reference in
    ``precision``."""
    preps = [pp for pp, _ in cases]
    jobs = [(k, a) for k, (_, alphas) in enumerate(cases) for a in alphas]
    low = R.walk_many(preps, [(k, R.bf16(a)) for k, a in jobs],
                      rnd=R.bf16, workers=workers)
    ref = EG.sweep_makespans(preps, jobs, precision, workers)
    chk = EG.RoundedChecker(limits, EG.ROUNDING[precision])
    at = 0
    for pp, alphas in cases:
        mine = low[at:at + len(alphas)]
        ref_ms = ref[at:at + len(alphas)]
        at += len(alphas)
        best = 0            # the policy's rule: strict improvement
        for a in range(1, len(alphas)):
            if mine[a][0] < mine[best][0] - 1e-12:
                best = a
        ms, proc, start, finish = mine[best]
        chk.sweep(ref_ms, [w[0] for w in mine], ms, "control")
        chk.schedule(pp, alphas[best], proc, start, finish, ms, "control")
    return dict(chk.worst)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--workers", type=int, default=os.cpu_count())
    args = ap.parse_args()
    import run
    from loads import ep_step_closed

    found = run.load_cell(WORKLOAD)
    cfg, tr = found["config"], found["traffic"]
    low = {k: float("inf") for k in NUMBERS}
    for seed in (int(s) for s in args.seeds.split(",")):
        cases = ep_step_closed.control_cases(cfg, tr, seed)
        got = readings(cases, cfg["limits"], cfg["check"]["precision"],
                       args.workers)
        print(json.dumps({"seed": seed, "plans": len(cases), **got}),
              flush=True)
        low = {k: min(low[k], got[k]) for k in NUMBERS}
    print(json.dumps({"control_min": low, "limits": cfg["limits"]}))


if __name__ == "__main__":
    main()
