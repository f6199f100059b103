"""Plain reference scheduler: HVLB_CC(B) on plain data (arXiv 1705.00307).

A straightforward reading of the paper's equations that imports nothing
of the system under test: Eq. 2 ranks per source processor (with the
Eq. 3-6 route and processor speeds), the Eq. 9 HPRV(B) queue, the
Eq. 16 LDET, and the Sec. 4.2-4.3 processor selection with contended
message routing (Eqs. 10-15, Defs. 4.1-4.2).

``walk`` is the one scheduling loop.  Left to itself it is the
reference list schedule.  Given another implementation's decisions
(``forced``) it evaluates every candidate of every decision on the
reference's own state, measures how far the forced choice lies from the
reference's best, commits the forced choice, and goes on: that is how a
float32 schedule is held to the float64 reference decision by decision,
even after a legitimate near-tie flip.

``rnd`` rounds every arithmetic result of the scheduling loop (the
control runs it in bfloat16); ranks, queue and LDET stay float64, as
they are host inputs to the loop.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

Route = Tuple[str, ...]


@dataclasses.dataclass
class Cluster:
    """Processors with execution rates, links with speeds, routes."""

    rates: List[float]
    link_speed: Dict[str, float]
    routes: Dict[Tuple[int, int], List[Route]]
    proc_speed: List[float]

    @property
    def n_procs(self) -> int:
        return len(self.rates)


def switched_cluster(rates: Sequence[float],
                     speeds: Sequence[float]) -> Cluster:
    """One switch: processor ``p`` hangs off link ``l{p+1}``; each pair
    has one two-link route.  Speeds per Eqs. 3-5: a route runs at its
    slowest link, a processor at the mean over its routes."""
    P = len(rates)
    link_speed = {f"l{k + 1}": float(s) for k, s in enumerate(speeds)}
    routes: Dict[Tuple[int, int], List[Route]] = {}
    for a in range(P):
        for b in range(P):
            if a != b:
                routes[(a, b)] = [(f"l{a + 1}", f"l{b + 1}")]
    proc_speed = []
    for src in range(P):
        per_dst = [float(np.mean([min(link_speed[l] for l in r)
                                  for r in routes[(src, d)]]))
                   for d in range(P) if d != src]
        proc_speed.append(float(np.mean(per_dst)))
    return Cluster([float(r) for r in rates], link_speed, routes,
                   proc_speed)


@dataclasses.dataclass
class Graph:
    """Task DAG: volumes ``weights``, edge volumes ``tpl``."""

    n: int
    edges: List[Tuple[int, int]]
    weights: np.ndarray
    tpl: Dict[Tuple[int, int], float]

    def __post_init__(self) -> None:
        self.weights = np.asarray(self.weights, dtype=float)
        self.succ: List[List[int]] = [[] for _ in range(self.n)]
        self.pred: List[List[int]] = [[] for _ in range(self.n)]
        for (i, j) in self.edges:
            self.succ[i].append(j)
            self.pred[j].append(i)
        indeg = [len(p) for p in self.pred]
        stack = [i for i in range(self.n) if indeg[i] == 0]
        self.topo: List[int] = []
        while stack:
            u = stack.pop()
            self.topo.append(u)
            for v in self.succ[u]:
                indeg[v] -= 1
                if indeg[v] == 0:
                    stack.append(v)
        assert len(self.topo) == self.n, "graph has a cycle"
        self.depth = np.ones(self.n, dtype=int)
        for u in self.topo:
            for v in self.succ[u]:
                self.depth[v] = max(self.depth[v], self.depth[u] + 1)

    def comp(self, i: int, p: int, c: Cluster) -> float:
        """Eq. 1: computation time of task ``i`` on processor ``p``."""
        return float(self.weights[i]) / float(c.rates[p])


# ------------------------------------------------------ task priorities
def ranks(g: Graph, c: Cluster) -> np.ndarray:
    """Eq. 2: rank of each task per source processor, exits first."""
    P = c.n_procs
    rank = np.zeros((g.n, P))
    speeds = np.array(c.proc_speed)
    for u in reversed(g.topo):
        for p in range(P):
            cu = g.comp(u, p, c)
            if not g.succ[u]:
                rank[u, p] = cu
                continue
            best = 0.0
            for v in g.succ[u]:
                best = max(best, rank[v, p] + g.tpl[(u, v)] / speeds[p])
            rank[u, p] = cu + best
    return rank


def queue_b(g: Graph, rank: np.ndarray) -> List[int]:
    """Eq. 9, HPRV(B) = hrank / depth^2 for non-exits, 0 for exits;
    non-increasing, ties by hrank, then task id."""
    h = rank.mean(axis=1)
    outd = np.array([len(s) for s in g.succ], dtype=float)
    prv = h * (outd > 0).astype(float) / (g.depth.astype(float) ** 2)
    return sorted(range(g.n),
                  key=lambda i: (-round(prv[i], 6), -round(h[i], 6), i))


def ldet(g: Graph, c: Cluster, rank: np.ndarray) -> np.ndarray:
    """Eq. 16: rank minus own computation; 1 for exit tasks."""
    comp = np.array([[g.comp(i, p, c) for p in range(c.n_procs)]
                     for i in range(g.n)])
    out = rank - comp
    for i in range(g.n):
        if not g.succ[i]:
            out[i] = 1.0
    return out


def default_period(g: Graph, c: Cluster) -> float:
    """Def. 4.1's period: the sum of each task's fastest computation."""
    return float(sum(min(g.comp(i, p, c) for p in range(c.n_procs))
                     for i in range(g.n)))


@dataclasses.dataclass
class Prepared:
    """Everything the scheduling loop needs besides alpha."""

    g: Graph
    c: Cluster
    queue: List[int]
    ldet: np.ndarray
    period: float


def prepare(g: Graph, c: Cluster,
            period: Optional[float] = None) -> Prepared:
    rank = ranks(g, c)
    return Prepared(g, c, queue_b(g, rank), ldet(g, c, rank),
                    default_period(g, c) if period is None else period)


# ---------------------------------------------------- scheduling loop
def _exact(x: float) -> float:
    return x


@dataclasses.dataclass
class Walk:
    """One schedule (for a forced walk: the reference's own times for
    the forced decisions) and how far the forced decisions sit from the
    reference, decision by decision."""

    proc: np.ndarray
    start: np.ndarray
    finish: np.ndarray
    # forced: worst relative selection-value gap of a forced choice
    # above the reference's best candidate (0 when it is the best)
    decision_gap: float = 0.0
    # forced: worst |forced start/finish - reference EST/EFT of the
    # same choice|, relative to the makespan
    time_err: float = 0.0

    @property
    def makespan(self) -> float:
        return float(self.finish.max())


def walk(pp: Prepared, alpha: float,
         forced: Optional[Tuple[Sequence[int], Sequence[float],
                                Sequence[float]]] = None,
         rnd: Callable[[float], float] = _exact) -> Walk:
    """List-schedule ``pp.queue`` at ``alpha`` (Secs. 4.2-4.3).

    Each candidate ``p`` of task ``j``: the task's incoming messages,
    in (predecessor finish, id) order, are routed on the route with the
    earliest arrival (ties: fewer hops, route order) with per-link
    contention (Eqs. 13-15); EST is the later of processor free time
    and the last arrival (Eqs. 10-11), EFT = EST + computation (Eq. 12);
    the selection value is EFT * LDET * BP (Def. 4.2; EFT alone for an
    exit task), BP = 1 + load/period * alpha (Def. 4.1).  The winner is
    the lexicographic (value, EFT, processor) minimum.
    """
    g, c, P = pp.g, pp.c, pp.c.n_procs
    period = rnd(pp.period)
    comp = [[rnd(g.comp(i, p, c)) for p in range(P)] for i in range(g.n)]
    ld = [[rnd(float(x)) for x in row] for row in pp.ldet]
    tpl = {e: rnd(v) for e, v in g.tpl.items()}
    proc_free = [0.0] * P
    loads = [0.0] * P
    link_free: Dict[str, float] = {}
    proc_of = [-1] * g.n
    ast = np.zeros(g.n)
    aft = np.zeros(g.n)
    out = Walk(proc=np.zeros(g.n, dtype=int), start=ast, finish=aft)
    errs: List[float] = []
    for j in pp.queue:
        order = sorted(g.pred[j], key=lambda i: (aft[i], i))
        cands = []
        for p in range(P):
            tentative = dict(link_free)
            arrival = 0.0
            for i in order:
                src = proc_of[i]
                assert src >= 0, f"task {j} queued before predecessor {i}"
                if src == p:
                    arrival = max(arrival, aft[i])
                    continue
                best_key = None
                for ridx, route in enumerate(c.routes[(src, p)]):
                    ivs, lst, lft = [], 0.0, 0.0
                    for h, l in enumerate(route):
                        avail = tentative.get(l, 0.0)
                        lst = max(aft[i] if h == 0 else lst, avail)
                        lft = max(lft, rnd(lst + rnd(tpl[(i, j)]
                                                     / c.link_speed[l])))
                        ivs.append((l, lft))
                    key = (lft, len(route), ridx)
                    if best_key is None or key < best_key:
                        best_key, best_ivs = key, ivs
                for (l, f) in best_ivs:
                    tentative[l] = max(tentative.get(l, 0.0), f)
                arrival = max(arrival, best_key[0])
            est = max(proc_free[p], arrival)
            eft = rnd(est + comp[j][p])
            if not g.succ[j]:
                value = eft
            else:
                bp = rnd(1.0 + rnd(rnd(loads[p] / period) * alpha))
                value = rnd(rnd(eft * ld[j][p]) * bp)
            cands.append((value, eft, p, est, tentative))
        pick = min(cands, key=lambda t: t[:3])
        v0 = pick[0]
        if forced is not None:
            pick = cands[int(forced[0][j])]
            out.decision_gap = max(out.decision_gap,
                                   (pick[0] - v0) / abs(v0))
            errs.append(max(abs(float(forced[1][j]) - pick[3]),
                            abs(float(forced[2][j]) - pick[1])))
        value, eft, p, est, tentative = pick
        proc_of[j] = p
        ast[j], aft[j] = est, eft
        proc_free[p] = eft
        loads[p] = rnd(loads[p] + comp[j][p])
        link_free = tentative
    out.proc = np.array(proc_of)
    if forced is not None:
        out.time_err = max(errs) / float(aft.max())
    return out


def best_of(pp: Prepared, alphas: Sequence[float],
            rnd: Callable[[float], float] = _exact
            ) -> Tuple[float, List[Walk]]:
    """The alpha sweep: a schedule per alpha, the best by strict
    improvement in makespan over the grid order."""
    walks = [walk(pp, a, rnd=rnd) for a in alphas]
    best = 0
    for k, w in enumerate(walks):
        if w.makespan < walks[best].makespan - 1e-12:
            best = k
    return alphas[best], walks


_PREPS: list = []           # a worker process's prepared graphs


def _init(preps) -> None:
    _PREPS[:] = preps


def _walk_job(job):
    k, alpha, rnd = job
    w = walk(_PREPS[k], alpha, rnd=rnd)
    return w.makespan, w.proc, w.start, w.finish


def walk_many(preps: Sequence[Prepared], jobs: Sequence[Tuple[int, float]],
              rnd: Callable[[float], float] = _exact, workers: int = 1
              ) -> List[Tuple[float, np.ndarray, np.ndarray, np.ndarray]]:
    """``(makespan, proc, start, finish)`` of ``walk(preps[k], alpha)``
    for each ``(k, alpha)`` job, in order; over ``workers`` spawned
    processes (host arithmetic only: they import no JAX) when there are
    more than a few jobs for each."""
    workers = min(workers, len(jobs) // 8)
    if workers <= 1:
        return [(w.makespan, w.proc, w.start, w.finish)
                for w in (walk(preps[k], float(a), rnd=rnd)
                          for k, a in jobs)]
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    with ProcessPoolExecutor(
            max_workers=workers, initializer=_init, initargs=(preps,),
            mp_context=multiprocessing.get_context("spawn")) as ex:
        return list(ex.map(_walk_job, [(k, float(a), rnd)
                                       for k, a in jobs], chunksize=4))


def bf16(x: float) -> float:
    """Round to the nearest bfloat16 (ties to even)."""
    b = np.array([x], dtype=np.float32).view(np.uint32)
    b = ((b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1)))
         & np.uint32(0xFFFF0000))
    return float(b.view(np.float32)[0])
