"""TGFF-style random stream-processing graphs (arXiv 1705.00307 §5.2).

A layered random DAG with capped in- and out-degree, at least two entry
and two exit tasks, task volumes U(w_lo, w_hi) and edge volumes scaled
to a communication-to-computation ratio (CCR) on a given cluster.  The
same draws as the system's own generator, so one seed gives one graph
on either side; kept here so that the benchmark's data cannot move with
the program.  It tries up to 2000 layerings where the program's stops
at 200: at 300 tasks under the caps 2/3 about one layering in 22 can be
wired, so 200 tries fail about once in 10^4 graphs.
"""
from __future__ import annotations

import numpy as np

from .reference import Cluster, Graph


def random_graph(n: int, rng: np.random.Generator, c: Cluster, *,
                 max_in: int, max_out: int, ccr: float,
                 w_lo: float = 5.0, w_hi: float = 25.0,
                 min_entries: int = 2, min_exits: int = 2) -> Graph:
    for _attempt in range(2000):
        edges = _try_random(n, rng, max_in, max_out, min_entries,
                            min_exits)
        if edges is None:
            continue
        weights = rng.uniform(w_lo, w_hi, size=n)
        g = Graph(n, edges, weights, {})
        _assign_tpl(g, rng, ccr, c)
        return g
    raise RuntimeError("could not generate a graph with the constraints")


def _try_random(n, rng, max_in, max_out, min_entries, min_exits):
    n_levels = max(2, int(round(np.sqrt(n))) + rng.integers(0, 2))
    levels = np.sort(rng.integers(0, n_levels, size=n))
    levels[:min_entries] = 0
    levels[-min_exits:] = n_levels - 1
    edges = []
    ind = np.zeros(n, dtype=int)
    outd = np.zeros(n, dtype=int)
    order = np.arange(n)
    for j in order:
        if levels[j] == 0:
            continue
        cands = np.flatnonzero((levels < levels[j]) & (outd < max_out))
        if not len(cands):
            return None
        k = min(int(rng.integers(1, max_in + 1)), len(cands))
        for i in rng.choice(cands, size=k, replace=False):
            edges.append((int(i), int(j)))
            ind[j] += 1
            outd[i] += 1
    # every non-exit task must reach somewhere: attach dangling tasks
    top, lv = levels.max(), levels.tolist()
    for i in order:
        if lv[i] < top and outd[i] == 0:
            cands = np.flatnonzero((levels > levels[i]) & (ind < max_in))
            if len(cands):
                j = int(rng.choice(cands))
                edges.append((int(i), j))
                ind[j] += 1
                outd[i] += 1
                continue
            # every later task is at full in-degree: take an in-slot from
            # a predecessor that can spare an out-edge (caps preserved)
            swaps = [(ii, j) for (ii, j) in edges
                     if lv[j] > lv[i] and outd[ii] > 1]
            if not swaps:
                return None
            ii, j = swaps[int(rng.integers(len(swaps)))]
            edges.remove((ii, j))
            outd[ii] -= 1
            edges.append((int(i), int(j)))
            outd[i] += 1
    return edges


def _assign_tpl(g: Graph, rng: np.random.Generator, ccr: float,
                c: Cluster) -> None:
    """Edge volumes U(0.5, 1.5) x CCR x mean computation x mean speed."""
    P = c.n_procs
    mean_comp = float(np.mean([[g.comp(i, p, c) for p in range(P)]
                               for i in range(g.n)]))
    mean_speed = float(np.mean(c.proc_speed))
    target = ccr * mean_comp * mean_speed
    for e in g.edges:
        g.tpl[e] = float(rng.uniform(0.5, 1.5) * target)

