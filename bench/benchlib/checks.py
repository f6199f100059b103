"""How ``correct`` is decided: served plans held to the plain reference.

Every plan checked is walked by the reference with the plan's own
decisions forced (``reference.walk``).  Three numbers, each the worst
over the plans checked in a run, are compared with limits of their own
that the configuration file states (``limits``), and one count:

* ``decision_gap``: how far a served processor choice lies above the
  reference's best candidate, as a share of the best selection value.
  The float32 device path may resolve a near tie either way (its
  documented policy), so this reads about 0 and never more than the
  near-tie tolerance.
* ``time_err``: the worst difference between a served start or finish
  time and the reference's own time for the same decisions, as a share
  of the makespan.
* ``makespan_err``: the served makespan against the reference's
  makespan of the served decisions, as a share of the latter.
* ``sweep_off_share``: of every alpha of every plan's sweep, the share
  whose makespan differs from the reference's own schedule at that
  alpha by more than the ``makespan_err`` limit.  A near tie that the
  float32 path resolves the other way sends an alpha's schedule
  elsewhere, so this reads a little above 0, never 0 by rule.
* ``best_gap``: the served makespan against the best makespan of the
  reference over the whole grid, as a share of the latter.
* ``faults``: answers that are missing, failed, or disagree with
  themselves (an alpha off the grid, a chosen alpha that is not the
  best of the plan's own sweep, a backend demotion).  Limit 0.
"""
from __future__ import annotations

import sys
from typing import Dict, List, Sequence, Tuple

from .reference import Prepared, walk

NUMBERS = ("decision_gap", "time_err", "makespan_err", "sweep_off_share",
           "best_gap")


class Checker:
    def __init__(self, limits: Dict[str, float]) -> None:
        self.limits = {k: float(limits[k]) for k in NUMBERS}
        self.worst = {k: 0.0 for k in NUMBERS}
        self.faults: List[str] = []
        self.checked = 0
        self.sweep_alphas = self.sweep_off = 0

    def fault(self, msg: str) -> None:
        self.faults.append(msg)

    def schedule(self, pp: Prepared, alpha: float, proc: Sequence[int],
                 start: Sequence[float], finish: Sequence[float],
                 makespan: float, what: str) -> None:
        """Hold one served schedule to the reference at its alpha."""
        if len(proc) != pp.g.n or not all(
                0 <= int(p) < pp.c.n_procs for p in proc):
            self.fault(f"{what}: processors out of range")
            return
        w = walk(pp, float(alpha), forced=(proc, start, finish))
        self.checked += 1
        for k, v in (("decision_gap", w.decision_gap),
                     ("time_err", w.time_err),
                     ("makespan_err",
                      abs(float(makespan) - w.makespan) / w.makespan)):
            self.worst[k] = max(self.worst[k], float(v))

    def sweep(self, reference: Sequence[float], served: Sequence[float],
              makespan: float, what: str) -> None:
        """Hold a plan's whole sweep to the reference's makespans at the
        same alphas, and its makespan to the reference's best."""
        if len(served) != len(reference):
            self.fault(f"{what}: {len(served)} sweep makespans for "
                       f"{len(reference)} alphas")
            return
        tol = self.limits["makespan_err"]
        self.sweep_alphas += len(reference)
        self.sweep_off += sum(1 for r, v in zip(reference, served)
                              if not abs(float(v) - r) <= tol * r)
        best = min(reference)
        self.worst["best_gap"] = max(self.worst["best_gap"],
                                     abs(float(makespan) - best) / best)
        self.worst["sweep_off_share"] = self.sweep_off / self.sweep_alphas

    def rows(self) -> List[Tuple[str, float, float]]:
        return [(k, self.worst[k], self.limits[k]) for k in NUMBERS] + \
            [("faults", float(len(self.faults)), 0.0)]

    @property
    def correct(self) -> bool:
        return self.checked > 0 and all(v <= lim
                                        for _, v, lim in self.rows())

    def report(self) -> Dict[str, Dict[str, float]]:
        """Print each number beside its limit (the last stderr lines)
        and return them for the result line."""
        for msg in self.faults[:20]:
            print(f"fault: {msg}", file=sys.stderr)
        print(f"check plans_checked={self.checked}", file=sys.stderr)
        out = {}
        for k, v, lim in self.rows():
            print(f"check {k}={v!r} limit={lim!r}", file=sys.stderr)
            out[k] = {"value": v, "limit": lim}
        sys.stderr.flush()
        return out
