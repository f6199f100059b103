"""HVLB_CC-driven placement of stage graphs onto TPU mesh slices.

The production mesh is carved into pipeline slices ("processors" in the
paper's model).  Slice execution rates come from chips x peak x MFU —
heterogeneity enters through degraded slices (stragglers, mixed
generations).  Links: intra-pod slice boundaries ride ICI; the pod
boundary rides shared DCN (the "gateway" of the paper's Fig. 2 — a slower
shared bus with real contention).

``tpu_mesh_topology`` is the other layout: the chips of one slice as
processors on its 2D ICI mesh, one link per mesh edge, each pair joined
by its dimension-order routes (the expert-parallel deployments of
``taskgraph.ep_step_graph``).

``plan_placement`` runs HSV_CC (baseline) and HVLB_CC (A/B) on the graph
and returns assignments + predicted step makespans.  Re-planning with
measured rates is the framework's straggler-mitigation path: static
re-scheduling, exactly the paper's answer for time-predictable systems.
``backend=`` threads through to the engine's candidate-evaluation layer
("auto"/"scalar"/"vector"/"pallas" — see DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import (HSV_CC, HVLB_CC_A, HVLB_CC_B, Plan, Scheduler,
                        Topology, load_balance)
from repro.core.graph import SPG
from repro.core.scheduler import Schedule

from .cost_model import HW


def tpu_slice_topology(n_slices: int = 8, chips_per_slice: int = 64,
                       pods: int = 2, hw: HW = HW(),
                       degraded: Optional[Dict[int, float]] = None
                       ) -> Topology:
    """Slices on a ring of ICI links; one shared DCN bus joins the pods.

    Link speeds are bytes/s; task weights are FLOPs and rates FLOP/s, so
    all schedule times come out in seconds.
    """
    degraded = degraded or {}
    rates = np.array([chips_per_slice * hw.peak_flops * hw.mfu *
                      degraded.get(i, 1.0) for i in range(n_slices)])
    per_pod = n_slices // pods
    links: Dict[str, float] = {}
    routes: Dict[Tuple[int, int], List[Tuple[str, ...]]] = {}
    # ICI boundary link between adjacent slices within a pod; the slice
    # boundary crosses `chips_per_slice`-worth of ICI edge bandwidth.
    ici_boundary = hw.ici_bw * hw.ici_links * np.sqrt(chips_per_slice)
    for i in range(n_slices - 1):
        same_pod = (i // per_pod) == ((i + 1) // per_pod)
        links[f"l{i}"] = ici_boundary if same_pod else hw.dcn_bw * 8
    # single shared DCN bus for any cross-pod hop (contention point)
    links["dcn"] = hw.dcn_bw * 8
    for a in range(n_slices):
        for b in range(a + 1, n_slices):
            if (a // per_pod) == (b // per_pod):
                routes[(a, b)] = [tuple(f"l{i}" for i in range(a, b))]
            else:
                pre = tuple(f"l{i}" for i in range(a, per_pod * (a // per_pod + 1) - 1))
                post = tuple(f"l{i}" for i in range(per_pod * (b // per_pod), b))
                routes[(a, b)] = [pre + ("dcn",) + post]
    return Topology([f"slice{i}" for i in range(n_slices)], rates, links,
                    routes)


def tpu_mesh_topology(nx: int, ny: int, hw: HW = HW(),
                      degraded: Optional[Dict[int, float]] = None
                      ) -> Topology:
    """The chips of an ``nx`` x ``ny`` ICI mesh (no wraparound) as
    processors.

    Chip ``(x, y)`` is processor ``y * nx + x`` and runs at ``peak_flops
    * mfu`` (times ``degraded[p]`` for a throttled chip ``p``).  Each mesh
    edge is one link at ``hw.ici_bw`` bytes/s, shared by both directions:
    ``x{x}.{y}`` joins ``(x, y)`` and ``(x + 1, y)``, ``y{x}.{y}`` joins
    ``(x, y)`` and ``(x, y + 1)``.  A pair of chips has its two
    dimension-order routes, X then Y first and Y then X second, or the
    one straight route where the chips share a row or a column; a route
    of ``|dx| + |dy|`` hops, so up to ``nx + ny - 2``.
    """
    degraded = degraded or {}
    P = nx * ny
    if nx < 1 or ny < 1:
        raise ValueError(f"a mesh needs at least one chip, got {nx}x{ny}")
    bad = [p for p in degraded if not 0 <= p < P]
    if bad:
        raise ValueError(f"degraded chips {bad} are not in the {nx}x{ny} "
                         f"mesh")
    rates = np.array([hw.peak_flops * hw.mfu * degraded.get(p, 1.0)
                      for p in range(P)])
    links: Dict[str, float] = {}
    for y in range(ny):
        for x in range(nx):
            if x + 1 < nx:
                links[f"x{x}.{y}"] = hw.ici_bw
            if y + 1 < ny:
                links[f"y{x}.{y}"] = hw.ici_bw

    def walk_x(x0: int, x1: int, y: int) -> List[str]:
        step = 1 if x1 > x0 else -1
        return [f"x{min(x, x + step)}.{y}" for x in range(x0, x1, step)]

    def walk_y(y0: int, y1: int, x: int) -> List[str]:
        step = 1 if y1 > y0 else -1
        return [f"y{x}.{min(y, y + step)}" for y in range(y0, y1, step)]

    routes: Dict[Tuple[int, int], List[Tuple[str, ...]]] = {}
    for a in range(P):
        ax, ay = a % nx, a // nx
        for b in range(P):
            if a == b:
                continue
            bx, by = b % nx, b // nx
            xy = tuple(walk_x(ax, bx, ay) + walk_y(ay, by, bx))
            yx = tuple(walk_y(ay, by, ax) + walk_x(ax, bx, by))
            routes[(a, b)] = [xy] if xy == yx else [xy, yx]
    return Topology([f"chip{p}" for p in range(P)], rates, links, routes)


@dataclasses.dataclass
class PlacementPlan:
    schedule: Schedule
    algorithm: str
    makespan_s: float
    load_balance: float
    assignment: Dict[int, int]          # stage -> slice
    # the scheduler's plan: the alpha sweep, alpha*, replay counts, the
    # backend that ran and any fallback it took
    plan: Plan

    @property
    def stage_map(self) -> Dict[int, List[int]]:
        out: Dict[int, List[int]] = {}
        for t, s in self.assignment.items():
            out.setdefault(s, []).append(t)
        return out


def _policy_for(algorithm: str, alpha_max: float):
    if algorithm == "hsv":
        return HSV_CC()
    if algorithm == "hvlb_a":
        return HVLB_CC_A(alpha_max=alpha_max, alpha_step=0.05)
    if algorithm == "hvlb_b":
        return HVLB_CC_B(alpha_max=alpha_max, alpha_step=0.05)
    raise ValueError(algorithm)


def plan_placement(g: SPG, tg: Topology, algorithm: str = "hvlb_b",
                   alpha_max: float = 3.0,
                   engine: str = "compiled",
                   backend: Optional[str] = None) -> PlacementPlan:
    """Place ``g`` on ``tg`` with one ``Scheduler.submit``.

    ``"hsv"`` is HSV_CC (alpha 0); ``"hvlb_a"`` and ``"hvlb_b"`` sweep
    HVLB_CC's alpha over the grid ``0, 0.05, ..., alpha_max`` (``k *
    0.05`` for ``k = 0 .. round(alpha_max / 0.05)``: 61 points at the
    default 3.0, 101 at 5.0) and keep the best makespan; on the device
    backend that is the fused sweep, one dispatch.  The plan's sweep,
    alpha* and backend are on ``PlacementPlan.plan``.
    """
    sched = Scheduler(tg, policy=_policy_for(algorithm, alpha_max),
                      engine=engine, backend=backend)
    plan = sched.submit(g)
    s = plan.schedule
    return PlacementPlan(
        schedule=s, algorithm=algorithm, makespan_s=s.makespan,
        load_balance=load_balance(s),
        assignment={i: int(s.proc[i]) for i in range(g.n)}, plan=plan)


def replan(g: SPG, tg: Topology, measured_rates: Sequence[float],
           algorithm: str = "hvlb_b",
           engine: str = "compiled",
           backend: Optional[str] = None) -> PlacementPlan:
    """Straggler mitigation: re-run the static scheduler with observed
    slice rates (the paper's time-predictable alternative to dynamic
    work stealing)."""
    tg2 = Topology(tg.proc_names, np.asarray(measured_rates, float),
                   dict(tg.link_speed), dict(tg.routes),
                   ctml_mode=tg.ctml_mode)
    return plan_placement(g, tg2, algorithm, engine=engine, backend=backend)
