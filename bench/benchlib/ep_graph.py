"""Expert-parallel prefill steps of an MoE model on a 2D ICI mesh: the
benchmark's own draws, and its plain copy of the step graph and of the
mesh, built with its own formulas.  Imports nothing of the system under
test: the program builds its graph and topology from the same draws,
and the check compares the two.

A step (one cell configuration's ``model`` and ``step``): per layer and
microbatch, each data-parallel rank prefills one chunk of a prompt; its
attention task (attention over the chunk against the prompt so far,
plus the router) sends each token to ``top_k`` experts, and each expert
task sends its results back to the next layer's attention task of the
rank the token came from.

Draws, from one generator, per step:

* each (microbatch, rank) chunk's prompt offset: a multiple of the chunk
  below the context length, uniform, so the chunk attends to ``offset +
  chunk`` keys;
* per layer, the experts' popularity: Zipf with exponent ``zipf_s`` over
  a random permutation of the experts; each token of each chunk picks
  ``top_k`` distinct experts under it (Gumbel top-k, which is sampling
  without replacement in proportion to the popularity).

Graph (task ids layer-major, then microbatch, then the ranks' attention
tasks, then the experts; labels ``(layer, microbatch, kind, index)``):
weights are FLOPs, edge volumes bytes.

* attention of ``T`` queries against ``kv`` keys with ``H`` query heads,
  ``KV`` key/value heads of width ``dh`` and model width ``D``: the
  query and output projections ``2 * 2*T*D*H*dh``, the key and value
  projections ``2 * 2*T*D*KV*dh``, the scores and their product with the
  values ``2 * 2*T*kv*H*dh``; the router ``2*T*D*E``;
* an expert over ``n`` routed tokens, a gated MLP of width ``F``
  (gate, up and down): ``3 * 2*n*D*F``;
* a message of ``n`` tokens: ``n * D`` bf16 values, ``2*n*D`` bytes.

Residency: rank ``d``'s KV cache lives on chip ``d mod P``, expert
``e``'s weights on chip ``e mod P``.  A task's computation time on chip
``p`` (the graph's ``comp`` matrix) is its FLOPs over the chip's rate,
plus, off its home chip ``h``, the bytes it streams from home over the
speed of the pair's routes (Eq. 4: the mean over the routes of the
slowest link): an attention task its layer's KV cache, ``kv * 2 * KV *
dh`` bf16 values, ``4 * kv * KV * dh`` bytes; an expert task its
weights, ``3 * D * F`` int8 values and bytes.

Mesh: chip ``(x, y)`` is processor ``y * nx + x``; one link per mesh
edge, shared by both directions, named ``x{x}.{y}`` between ``(x, y)``
and ``(x + 1, y)`` and ``y{x}.{y}`` between ``(x, y)`` and ``(x, y +
1)``; each pair of chips has the route that moves along x first, then
the one that moves along y first (one route where they coincide).
"""
from __future__ import annotations

import dataclasses
import multiprocessing
from concurrent.futures import ProcessPoolExecutor
from typing import Any, Callable, Dict, List, Sequence, Tuple

import numpy as np

from .checks import Checker
from .reference import Cluster, Graph, Prepared, bf16, walk, walk_many

Label = Tuple[int, int, str, int]


def draw_step(rng: np.random.Generator, model: Dict[str, Any],
              step: Dict[str, Any]) -> Tuple[np.ndarray, np.ndarray]:
    """``(tokens, kv_len)`` of one step: ``tokens[l, m, d, e]`` routed
    from rank ``d``'s chunk of microbatch ``m`` to expert ``e`` at layer
    ``l``; ``kv_len[m, d]`` the keys that chunk attends to."""
    L, M, D = model["n_layers"], step["microbatches"], step["ranks"]
    E, k, T = model["n_experts"], model["top_k"], step["chunk"]
    kv_len = T * (1 + rng.integers(0, step["context"] // T, size=(M, D)))
    zipf = 1.0 / np.arange(1, E + 1) ** step["zipf_s"]
    zipf /= zipf.sum()
    tokens = np.zeros((L, M, D, E), dtype=np.int64)
    cell = np.arange(M * D).reshape(M, D, 1, 1) * E
    for l in range(L):
        pop = zipf[rng.permutation(E)]
        keys = np.log(pop) + rng.gumbel(size=(M, D, T, E))
        picked = np.argpartition(-keys, k - 1, axis=-1)[..., :k]
        tokens[l] = np.bincount((cell + picked).ravel(),
                                minlength=M * D * E).reshape(M, D, E)
    return tokens, kv_len


@dataclasses.dataclass
class ResidentGraph(Graph):
    """``Graph`` whose computation times are a ``(tasks, processors)``
    matrix (the residency above) instead of weight over rate."""

    comp_matrix: np.ndarray = None

    def comp(self, i: int, p: int, c: Cluster) -> float:
        return float(self.comp_matrix[i, p])


def step_graph(model: Dict[str, Any], tokens: np.ndarray,
               kv_len: np.ndarray, c: Cluster
               ) -> Tuple[ResidentGraph, List[Label]]:
    """The reference graph of a step on the chips of ``c`` and each
    task's label."""
    L, M, D, E = tokens.shape
    P = c.n_procs
    Dm, F = model["d_model"], model["d_ff"]
    H, KV, dh = model["n_heads"], model["n_kv_heads"], model["head_dim"]
    T = int(tokens[0, 0, 0].sum()) // model["top_k"]

    def attention(kv: int) -> float:
        return (2 * 2 * T * Dm * H * dh + 2 * 2 * T * Dm * KV * dh
                + 2 * 2 * T * kv * H * dh + 2 * T * Dm * E)

    def block(l: int, m: int) -> int:
        return (l * M + m) * (D + E)

    n = L * M * (D + E)
    weights = np.zeros(n)
    labels: List[Label] = [(0, 0, "", 0)] * n
    homes = [0] * n
    stream = [0.0] * n              # bytes a task needs from home
    tpl: Dict[Tuple[int, int], float] = {}
    for l in range(L):
        for m in range(M):
            b = block(l, m)
            for d in range(D):
                weights[b + d] = attention(int(kv_len[m, d]))
                labels[b + d] = (l, m, "attn", d)
                homes[b + d] = d % P
                stream[b + d] = 4.0 * int(kv_len[m, d]) * KV * dh
            for e in range(E):
                x = b + D + e
                weights[x] = 3 * 2 * int(tokens[l, m, :, e].sum()) * Dm * F
                labels[x] = (l, m, "expert", e)
                homes[x] = e % P
                stream[x] = 3.0 * Dm * F
                for d in range(D):
                    vol = 2.0 * int(tokens[l, m, d, e]) * Dm
                    tpl[(b + d, x)] = vol
                    if l + 1 < L:
                        tpl[(x, block(l + 1, m) + d)] = vol
    speed = {(h, p): float(np.mean([min(c.link_speed[k] for k in r)
                                    for r in c.routes[(h, p)]]))
             for (h, p) in c.routes}
    comp = np.array([[float(weights[i]) / c.rates[p]
                      + (0.0 if p == homes[i]
                         else stream[i] / speed[(homes[i], p)])
                      for p in range(P)] for i in range(n)])
    return ResidentGraph(n, list(tpl), weights, tpl, comp), labels


def mesh_cluster(spec: Dict[str, Any]) -> Cluster:
    """The mesh of chips (module docstring): rates ``peak_flops * mfu``,
    times ``degraded[p]`` for a throttled chip; every link at
    ``link_bw``; route and processor speeds per Eqs. 3-5."""
    nx, ny = spec["nx"], spec["ny"]
    P = nx * ny
    slow = {int(p): float(f) for p, f in spec.get("degraded", {}).items()}
    rates = [spec["peak_flops"] * spec["mfu"] * slow.get(p, 1.0)
             for p in range(P)]
    bw = float(spec["link_bw"])
    link_speed = {}
    for y in range(ny):
        for x in range(nx):
            if x + 1 < nx:
                link_speed[f"x{x}.{y}"] = bw
            if y + 1 < ny:
                link_speed[f"y{x}.{y}"] = bw

    def along_x(x0: int, x1: int, y: int) -> List[str]:
        lo, hi = min(x0, x1), max(x0, x1)
        hops = [f"x{x}.{y}" for x in range(lo, hi)]
        return hops if x1 >= x0 else hops[::-1]

    def along_y(y0: int, y1: int, x: int) -> List[str]:
        lo, hi = min(y0, y1), max(y0, y1)
        hops = [f"y{x}.{y}" for y in range(lo, hi)]
        return hops if y1 >= y0 else hops[::-1]

    routes: Dict[Tuple[int, int], List[Tuple[str, ...]]] = {}
    for a in range(P):
        for b in range(P):
            if a == b:
                continue
            (ax, ay), (bx, by) = divmod(a, nx)[::-1], divmod(b, nx)[::-1]
            x_first = tuple(along_x(ax, bx, ay) + along_y(ay, by, bx))
            y_first = tuple(along_y(ay, by, ax) + along_x(ax, bx, by))
            routes[(a, b)] = [x_first] if x_first == y_first \
                else [x_first, y_first]
    proc_speed = [float(np.mean([np.mean([min(link_speed[l] for l in r)
                                          for r in routes[(a, b)]])
                                 for b in range(P) if b != a]))
                  if P > 1 else 0.0 for a in range(P)]
    return Cluster(rates, link_speed, routes, proc_speed)


# ------------------------------------------------ the check's host work
def f32(x: float) -> float:
    """Round to the nearest float32 (ties to even)."""
    return float(np.float32(x))


class RoundedChecker(Checker):
    """``Checker`` whose forced walk runs the reference's scheduling loop
    in a stated precision: every arithmetic result rounded by ``rnd``
    (``reference.walk``), alpha given as the device holds it."""

    def __init__(self, limits: Dict[str, float],
                 rnd: Callable[[float], float]) -> None:
        super().__init__(limits)
        self.rnd = rnd

    def schedule(self, pp: Prepared, alpha: float, proc: Sequence[int],
                 start: Sequence[float], finish: Sequence[float],
                 makespan: float, what: str) -> None:
        if len(proc) != pp.g.n or not all(
                0 <= int(p) < pp.c.n_procs for p in proc):
            self.fault(f"{what}: processors out of range")
            return
        w = walk(pp, self.rnd(alpha), forced=(proc, start, finish),
                 rnd=self.rnd)
        self.checked += 1
        for k, v in (("decision_gap", w.decision_gap),
                     ("time_err", w.time_err),
                     ("makespan_err",
                      abs(float(makespan) - w.makespan) / w.makespan)):
            self.worst[k] = max(self.worst[k], float(v))


_PREPS: List[Prepared] = []        # a worker process's prepared graphs
ROUNDING = {"float64": float, "float32": f32, "bfloat16": bf16}


def _init(preps: Sequence[Prepared]) -> None:
    _PREPS[:] = preps


def _forced(job: tuple) -> Any:
    """The checker's numbers for one served plan, ``(k, precision,
    limits, alpha, proc, start, finish, makespan, what)``: its decisions
    forced on the reference of prepared graph ``k`` in ``precision``."""
    k, precision, limits, alpha, proc, start, finish, makespan, what = job
    chk = RoundedChecker(limits, ROUNDING[precision])
    chk.schedule(_PREPS[k], alpha, proc, start, finish, makespan, what)
    return chk.worst, chk.checked, chk.faults


def forced_walks(preps: Sequence[Prepared], jobs: Sequence[tuple],
                 workers: int) -> List[Any]:
    """``_forced`` of each job, in order, over ``workers`` spawned host
    processes (reference arithmetic only: they import no JAX)."""
    if workers <= 1 or len(jobs) <= 1:
        _init(preps)
        return [_forced(j) for j in jobs]
    with ProcessPoolExecutor(
            max_workers=workers, initializer=_init, initargs=(preps,),
            mp_context=multiprocessing.get_context("spawn")) as ex:
        return list(ex.map(_forced, jobs))


def sweep_makespans(preps: Sequence[Prepared],
                    jobs: Sequence[Tuple[int, float]], precision: str,
                    workers: int) -> List[float]:
    """The reference's makespan of each ``(k, alpha)`` in ``precision``,
    alpha as that precision holds it (``reference.walk_many``)."""
    rnd = ROUNDING[precision]
    return [w[0] for w in walk_many(preps, [(k, rnd(a)) for k, a in jobs],
                                    rnd=rnd, workers=workers)]
