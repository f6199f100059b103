"""The work of an HVLB_CC alpha sweep, counted from the algorithm, and
the least time a chip could take for it.

Counted per alpha over every decision j and every candidate processor
p (arXiv 1705.00307): for each incoming edge (priced for every
candidate, the same-processor case included) and each route, Eq. 13's
two maxima and Eq. 14's add and maximum per hop, one comparison to pick
the route, one maximum into the arrival time; Eqs. 10-12's maximum and
add; Def. 4.2's two products (none for an exit task); one comparison in
the winner's argmin.  Per decision, Def. 4.1's load update: an add, a
division, a product and an add.

Bytes are what the sweep must move at least: its inputs read once
(computation and LDET matrices, edge volumes, link speeds) and, per
alpha, the schedule written once (processor, start, finish), four
bytes a value.  Padding and the implementation's own layout do not
count, so any implementation is read against the same work.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

from .reference import Cluster, Graph

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def work(g: Graph, c: Cluster, n_alphas: int) -> Tuple[float, float]:
    """(operations, bytes) of one sweep of ``n_alphas`` alphas."""
    P = c.n_procs
    # routes differ by (source, destination): price a message at the
    # mean over processor pairs of its route walk
    pairs = list(c.routes.values())
    route_ops = sum(sum(4 * len(r) + 1 for r in rr)
                    for rr in pairs) / len(pairs)
    per_alpha = 0.0
    for j in range(g.n):
        cand = len(g.pred[j]) * (route_ops + 1) \
            + 2 + (2 if g.succ[j] else 0) + 1
        per_alpha += P * cand + 4
    ops = float(per_alpha) * n_alphas
    nbytes = 4.0 * (2 * g.n * P + len(g.edges) + len(c.link_speed)) \
        + 4.0 * 3 * g.n * n_alphas
    return ops, nbytes


def peaks(device_kind: str) -> Dict[str, float]:
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peak FLOP/s and bandwidth for device kind "
                       f"{device_kind!r} in {PEAKS}")
    return table[device_kind]


def least_time(ops: float, nbytes: float,
               device_kind: str) -> Tuple[float, str]:
    """Seconds the chip needs at least, and which peak bounds it."""
    pk = peaks(device_kind)
    t_ops, t_bytes = ops / pk["flops_per_s"], nbytes / pk["bytes_per_s"]
    return (t_ops, "flops") if t_ops >= t_bytes else (t_bytes, "bytes")
