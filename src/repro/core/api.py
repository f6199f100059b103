"""Unified scheduler session API: policies, multi-graph submission, and
incremental rescheduling.

The paper's DSMS setting is *register once, execute continuously*
(Section 4.4): schedules are recomputed whenever queries are added or
task computation times drift.  This module is the long-lived surface for
that loop — a :class:`Scheduler` session bound to one
:class:`~.topology.Topology`:

  * ``submit(spg) -> Plan`` compiles and caches a
    :class:`~.engine.CompiledInstance` per graph and runs the selected
    :class:`Policy` (the Algorithm-1 alpha sweep for the HVLB policies).
  * ``submit_many([spg, ...]) -> FleetPlan`` schedules several
    independent SPGs against *shared* link state in one engine pass —
    the exp6 fleet-serving scenario.  Internally the graphs are joined
    into one disjoint-union SPG whose merged priority queue preserves
    each graph's own dequeue order.
  * ``update(task_rates=..., link_speed=...) -> Plan`` re-plans after
    drift.  For task-rate drift it re-simulates only the *suffix* of the
    memoized decision trace that the drift can actually reach: rows of
    the computation/LDET matrices that changed (plus, under the
    worked-example CCR convention, successors whose inbound message
    volumes changed) mark the first queue position whose decision could
    differ; everything before it is re-committed from the trace
    checkpoint (see ``engine.DecisionTrace``).  The result is
    bit-identical to a from-scratch ``submit`` of the modified graph.

Policies are frozen dataclasses (hashable — they key the session's plan
and trace caches): :class:`HSV_CC` (baseline, Xie et al.),
:class:`HVLB_CC_A` / :class:`HVLB_CC_B` (Algorithm 1 with the Eq. 8 /
Eq. 9 prioritizer), and :class:`HVLB_CC_IC` — the Section-4.4 imprecise
computation model as a first-class policy whose :class:`Plan` carries
schedule holes and precision accessors instead of requiring post-hoc
helper calls.

Every schedule ultimately runs on a *candidate-evaluation backend*
(:mod:`repro.core.backends`): ``backend="auto"`` (default) picks the
(P,)-batch vector backend on wide topologies and the scalar reference
loop otherwise; ``backend="pallas"`` (opt-in, requires jax) runs each
decision's candidate batch in a Pallas device kernel.  The NumPy
backends are bit-identical and pallas is decision-identical (DESIGN
§5), so the knob (session constructor, per-call override, or the
``REPRO_SCHED_BACKEND`` environment variable) is about speed, not
results.  Backend/topology compatibility is validated when the name
resolves — before any session state is built.

The pre-existing one-shot functions (``schedule_hsv_cc``,
``schedule_hvlb_cc``, ``schedule_hvlb_cc_best``) remain as thin
deprecation shims over this module with bit-identical outputs
(``tests/test_engine_equivalence.py`` asserts shim == session ==
reference).
"""
from __future__ import annotations

import dataclasses
import os
import warnings
from typing import Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np

from .. import tracing
from .backends import (PALLAS, default_backend, resolve_backend_name,
                       vector_compatible)
from .deprecation import warn_once
from .engine import (DEFAULT_BATCH_MAX, CompiledInstance, DecisionTrace,
                     validate_batch)
from .faults import (Fault, FaultSpec, InfeasibleScheduleError,
                     LinkDegraded, LinkDown, ProcessorDown)
from .graph import SPG
from .imprecise import precision as _precision
from .imprecise import schedule_holes
from .ranks import hprv_a, hprv_b, ldet_cc, priority_queue, rank_matrix
from .scheduler import Schedule, SchedulingFailure, list_schedule
from .topology import Topology
from .validate import (check_graph, check_link_speeds, check_task_rates,
                       check_topology)

# Grid alphas closer than this to a predicted trace-flip point are
# re-simulated rather than skipped (guards the last-ulp difference between
# the linear prediction A + B*alpha and the simulated Def. 4.1 value).
_SKIP_MARGIN = 1e-6

# Backends the session demotes away from when they fail mid-plan (the
# fallback chain, DESIGN.md §6): only opt-in *device* backends — a NumPy
# backend error is a real bug and must surface.
_DEVICE_BACKENDS = (PALLAS,)

# (from, to) pairs already warned about — the fallback chain warns once
# per process, not once per submit.
_FALLBACK_WARNED: set = set()


def _warn_fallback(src: str, dst: str, err: BaseException) -> None:
    key = (src, dst)
    if key in _FALLBACK_WARNED:
        return
    _FALLBACK_WARNED.add(key)
    warnings.warn(
        f"scheduler backend {src!r} failed "
        f"({type(err).__name__}: {err}); demoting to {dst!r} "
        f"(decisions are backend-identical; further demotions of this "
        f"kind stay silent)", RuntimeWarning, stacklevel=4)


# ----------------------------------------------------------------------
# Policies
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class HSV_CC:
    """Baseline policy (Xie et al. [25]): HPRV_A queue, EFT * LDET_CC
    selection — equivalent to HVLB_CC at alpha = 0, no sweep."""


@dataclasses.dataclass(frozen=True)
class HVLB_CC_A:
    """Algorithm 1 with the HSV prioritizer (Eq. 8): sweep alpha over
    ``[0, alpha_max]`` in ``alpha_step`` increments, keep min makespan.

    ``period`` is the application period of Definition 4.1 (the
    deadline/stream-rate requirement).  ``None`` pins the DAG's
    sum-of-min-computation proxy at first submission; the pinned value is
    reused by every :meth:`Scheduler.update` (``Plan.period`` exposes it).
    ``sweep="adaptive"`` is the opt-in coarse-to-fine grid.
    """

    alpha_max: float = 3.0
    alpha_step: float = 0.01
    period: Optional[float] = None
    sweep: str = "grid"
    coarse_factor: int = 10
    # adaptive-sweep refinement band: coarse grid points whose makespan is
    # within this *factor* of the coarse optimum get their neighbourhood
    # re-swept at the fine step (1.02 = the 2% band).  Pure sweep-cost
    # heuristic — it decides which alphas are simulated, never how any
    # committed decision is valued.
    refine_within: float = 1.02


@dataclasses.dataclass(frozen=True)
class HVLB_CC_B(HVLB_CC_A):
    """Algorithm 1 with the depth-damped prioritizer (Eq. 9) that orders
    arbitrary stream-processing graphs (see ``ranks.hprv_b``)."""

    depth_power: int = 2
    outd_mode: str = "indicator"


@dataclasses.dataclass(frozen=True)
class HVLB_CC_IC(HVLB_CC_B):
    """HVLB_CC (B) + the Section-4.4 imprecise-computation model: the
    resulting :class:`Plan` carries ``holes`` (Eqs. 20-21, with exit
    tasks that have nothing after them reported as ``inf``) and a
    ``precision(task, lam)`` accessor (Experiment 5)."""


Policy = Union[HSV_CC, HVLB_CC_A, HVLB_CC_B, HVLB_CC_IC]


# ----------------------------------------------------------------------
# Results
# ----------------------------------------------------------------------
@dataclasses.dataclass
class SweepResult:
    """Alpha-sweep outcome (Fig. 5 data), as plotting-ready arrays.

    ``alphas[k]`` / ``makespans[k]`` are the grid point and its makespan.
    The legacy list-of-tuples representation survives only as the
    deprecated :attr:`curve` property.
    """

    best: Schedule
    best_alpha: float
    alphas: np.ndarray                   # (k,) grid alphas
    makespans: np.ndarray                # (k,) makespan per grid alpha

    @classmethod
    def from_points(cls, best: Schedule, best_alpha: float,
                    points: List[Tuple[float, float]]) -> "SweepResult":
        """Build from the sweep loops' (alpha, makespan) accumulator."""
        return cls(best, best_alpha,
                   np.array([a for a, _ in points], dtype=float),
                   np.array([m for _, m in points], dtype=float))

    @property
    def curve(self) -> List[Tuple[float, float]]:
        """Deprecated list-of-tuples view; use ``alphas``/``makespans``."""
        warn_once("SweepResult.curve",
                  "SweepResult.curve is deprecated; use the "
                  "SweepResult.alphas / SweepResult.makespans arrays")
        return list(zip(self.alphas.tolist(), self.makespans.tolist()))


@dataclasses.dataclass
class ReplayStats:
    """Decision-replay accounting for one submit/update."""

    suffix_start: int            # first re-simulated queue position
    decisions_simulated: int     # full candidate-loop evaluations
    decisions_replayed: int      # positions re-committed from the trace
    sims_resumed: int            # alpha points resumed from a trace
    sims_full: int               # alpha points simulated from scratch
    # queue positions a fault event invalidated (len(queue) - suffix_start
    # on fault-triggered replans; 0 on submits and benign-drift updates):
    # the prefix-survival counter asserted by the chaos tests / exp9
    invalidated_by_fault: int = 0
    # perturbation events folded into this replay: 1 for a submit or a
    # plain single-dict update, k when a batched ``update`` coalesced k
    # task-rate/link-speed dicts into one combined suffix replay (the
    # service coalescing layer's replan-count lever, exp10)
    coalesced: int = 1


@dataclasses.dataclass
class Plan:
    """Result of scheduling one graph under one policy."""

    schedule: Schedule
    policy: Policy
    graph: SPG
    period: Optional[float]      # effective (pinned) Def.-4.1 period
    sweep: Optional[SweepResult] = None
    holes: Optional[Dict[int, float]] = None     # HVLB_CC_IC only
    replay: Optional[ReplayStats] = None
    backend: Optional[str] = None    # resolved evaluator ("reference": None)
    batch: Optional[int] = None      # resolved level-batch cap (reference:
    #                                  None; decisions are batch-invariant)
    # backend demotions taken to produce this plan, oldest first:
    # (from_backend, to_backend, reason) triples — None when the requested
    # backend ran clean.  ``backend`` above is the evaluator that actually
    # produced the schedule (decisions are backend-identical, so a demoted
    # plan's schedule equals the one the requested backend would have made).
    fallback: Optional[Tuple[Tuple[str, str, str], ...]] = None

    @property
    def makespan(self) -> float:
        return self.schedule.makespan

    @property
    def proc(self) -> np.ndarray:
        return self.schedule.proc

    @property
    def best_alpha(self) -> Optional[float]:
        return self.sweep.best_alpha if self.sweep is not None else None

    def precision(self, task: int, lam: float) -> float:
        """Data precision of ``task`` at arrival rate ``lam`` (Exp. 5).

        Requires an imprecise-computation policy (:class:`HVLB_CC_IC`),
        which attaches the schedule holes to the plan.
        """
        if self.holes is None:
            raise ValueError("precision requires an HVLB_CC_IC policy "
                             "(this plan carries no schedule holes)")
        s = self.schedule
        mp = self.graph.comp(task, int(s.proc[task]), s.topology.rates)
        return _precision(mp, self.holes.get(task, 0.0), lam, ic=True)


@dataclasses.dataclass
class FleetPlan:
    """Joint schedule of several independent SPGs on one topology.

    ``schedule`` is the union schedule (tasks of graph ``k`` occupy node
    ids ``offsets[k] .. offsets[k] + graphs[k].n``); ``subschedule(k)``
    re-indexes graph ``k``'s slice back to its own node ids.
    """

    schedule: Schedule
    graphs: List[SPG]
    offsets: List[int]
    policy: Policy
    period: Optional[float]
    sweep: Optional[SweepResult] = None
    backend: Optional[str] = None
    batch: Optional[int] = None
    fallback: Optional[Tuple[Tuple[str, str, str], ...]] = None

    @property
    def makespan(self) -> float:
        return self.schedule.makespan

    def subschedule(self, k: int) -> Schedule:
        g, off = self.graphs[k], self.offsets[k]
        lo, hi = off, off + g.n
        msgs = {(i - off, j - off): dataclasses.replace(
                    m, edge=(i - off, j - off))
                for (i, j), m in self.schedule.messages.items()
                if lo <= i < hi}
        return Schedule(g, self.schedule.topology,
                        self.schedule.proc[lo:hi].copy(),
                        self.schedule.start[lo:hi].copy(),
                        self.schedule.finish[lo:hi].copy(),
                        msgs, alpha=self.schedule.alpha)


# ----------------------------------------------------------------------
# Internals
# ----------------------------------------------------------------------
def _queue_key(policy: Policy) -> tuple:
    if isinstance(policy, HVLB_CC_B):        # covers HVLB_CC_IC
        return ("b", policy.depth_power, policy.outd_mode)
    return ("a",)                            # HSV_CC and HVLB_CC_A share Eq. 8


class _GraphSession:
    """Cached per-graph state of one Scheduler session.

    The compiled instance is built lazily: :meth:`Scheduler.probe_update`
    only needs ranks/LDET/queues to measure how much of a memoized trace
    a prospective drift would invalidate.
    """

    __slots__ = ("g", "handles", "rank", "ldet", "queues", "periods",
                 "traces", "plans", "_tg", "_compiled", "_inst", "_faults")

    def __init__(self, g: SPG, tg: Topology, compiled: bool,
                 faults: Optional[FaultSpec] = None,
                 rank: Optional[np.ndarray] = None,
                 ldet: Optional[np.ndarray] = None) -> None:
        self.g = g
        self.handles = [g]      # graph objects that address this session
        self._tg = tg
        self._compiled = compiled
        # active resource faults at session-build time; the compiled
        # instance embeds their masking, so the session cache is cleared
        # whenever the spec changes (Scheduler._fault_event).  Rank/LDET
        # stay those of the *healthy* system (DESIGN.md §6) and may be
        # handed over from a superseded session of the same (g, tg).
        self._faults = None if faults is None or faults.is_empty else faults
        self._inst: Optional[CompiledInstance] = None
        self.rank = rank_matrix(g, tg) if rank is None else rank
        self.ldet = ldet_cc(g, tg, self.rank) if ldet is None else ldet
        self.queues: Dict[tuple, List[int]] = {}
        self.periods: Dict[Policy, float] = {}
        # traces are shared across backends and batch caps (records are
        # backend-portable, decisions batch-invariant); plans are keyed
        # by (policy, backend, batch) so a per-call override never hands
        # back a stale plan object
        self.traces: Dict[Policy, Dict[float, DecisionTrace]] = {}
        self.plans: Dict[Tuple[Policy, Optional[str], Optional[int]],
                         Plan] = {}

    @property
    def inst(self) -> Optional[CompiledInstance]:
        if self._compiled and self._inst is None:
            with tracing.span("repro.api.prepare"):
                self._inst = CompiledInstance(
                    self.g, self._tg, rank=self.rank, ldet=self.ldet,
                    faults=self._faults)
        return self._inst

    def queue_for(self, tg: Topology, policy: Policy) -> List[int]:
        key = _queue_key(policy)
        q = self.queues.get(key)
        if q is None:
            with tracing.span("repro.api.prepare"):
                g, rank = self.g, self.rank
                if key[0] == "b":
                    prv = hprv_b(g, tg, rank, depth_power=policy.depth_power,
                                 outd_mode=policy.outd_mode)
                else:
                    prv = hprv_a(g, tg, rank)
                q = priority_queue(prv, rank.mean(axis=1))
            self.queues[key] = q
        return q

    def default_period(self, tg: Topology) -> float:
        return self.g.default_period(tg.rates, tg.n_procs)


def _rescaled_graph(g: SPG, events: Sequence[Dict[int, float]]) -> SPG:
    """The graph after arrival-rate drift: task ``t``'s computational
    volume scales by ``ev[t]`` for each event dict in order (Eq. 19's
    lambda on the mandatory part).  Factors are applied sequentially —
    ``(w * f1) * f2``, never ``w * (f1 * f2)`` — so one batched replay is
    bit-identical to replaying the events one ``update()`` at a time.
    Structure, explicit edge volumes, and names are preserved."""
    w = g.weights.copy()
    cm = None if g.comp_matrix is None else np.array(g.comp_matrix,
                                                     dtype=float)
    for ev in events:
        for t, f in ev.items():
            if not 0 <= t < g.n:
                raise ValueError(f"task {t} out of range")
            w[t] *= f
            if cm is not None:
                cm[t] *= f
    g2 = SPG(n=g.n, edges=list(g.edges), weights=w, tpl=dict(g.tpl),
             tpl_proportional_ccr=g.tpl_proportional_ccr,
             comp_matrix=cm, name=g.name)
    return g2


def _as_events(arg) -> List[dict]:
    """Normalize an ``update`` perturbation argument — one dict or a
    sequence of dicts (a batch of drift events, oldest first) — to a
    list of dicts."""
    if arg is None:
        return []
    if isinstance(arg, dict):
        return [arg]
    return [dict(ev) for ev in arg]


def _disjoint_union(graphs: Sequence[SPG], tg: Topology) -> Tuple[SPG,
                                                                  List[int]]:
    ccrs = {g.tpl_proportional_ccr for g in graphs}
    if len(ccrs) > 1:
        raise ValueError("submit_many requires every graph to share the "
                         "same tpl convention (tpl_proportional_ccr)")
    explicit = any(g.comp_matrix is not None for g in graphs)
    offsets: List[int] = []
    weights: List[float] = []
    edges: List[Tuple[int, int]] = []
    tpl: Dict[Tuple[int, int], float] = {}
    comp_rows: List[np.ndarray] = []
    off = 0
    for g in graphs:
        offsets.append(off)
        weights.extend(g.weights.tolist())
        edges.extend((i + off, j + off) for (i, j) in g.edges)
        tpl.update({(i + off, j + off): v for (i, j), v in g.tpl.items()})
        if explicit:
            comp_rows.append(g.comp_matrix_for(tg.rates))
        off += g.n
    union = SPG(n=off, edges=edges, weights=np.asarray(weights),
                tpl=tpl, tpl_proportional_ccr=next(iter(ccrs)),
                comp_matrix=np.vstack(comp_rows) if explicit else None,
                name=f"fleet[{len(graphs)}]")
    return union, offsets


# ----------------------------------------------------------------------
# The session
# ----------------------------------------------------------------------
class Scheduler:
    """Long-lived scheduling session bound to one :class:`Topology`.

    ``engine="compiled"`` (default) runs every policy on shared
    :class:`CompiledInstance` state with decision-trace memoization;
    ``engine="reference"`` re-runs the readable ``list_schedule`` per
    grid point (bit-identical results, no incremental replay — updates
    fall back to a full re-plan).

    ``backend`` selects the compiled engine's candidate-evaluation
    backend (:mod:`repro.core.backends`): ``"scalar"``, ``"vector"``,
    ``"pallas"`` (opt-in device kernel, requires jax), or ``"auto"``
    (the default — vector from P >= 8; overridable per process via the
    ``REPRO_SCHED_BACKEND`` environment variable).  The NumPy backends
    are bit-identical and pallas decision-identical, so this is a
    performance knob; ``submit``/``submit_many``/``update`` accept a
    per-call override.  An explicit backend incompatible with the
    session topology raises :class:`~.backends.BackendCompatError` at
    resolve time, leaving the session's caches untouched.

    ``batch`` caps the engine's level-batch size — how many independent
    same-rank-level tasks the decision layer hands to the backend per
    ``evaluate_batch`` wave (``None`` = the engine default,
    :data:`~.engine.DEFAULT_BATCH_MAX`; ``1`` = strict per-decision
    walk).  Decisions are batch-invariant, so this is purely a
    performance knob for device backends (one kernel launch and one
    host round-trip per wave); like ``backend`` it keys the plan cache
    and accepts a per-call override.
    """

    def __init__(self, topology: Topology, policy: Optional[Policy] = None,
                 engine: str = "compiled",
                 backend: Optional[str] = None,
                 batch: Optional[int] = None,
                 faults: Iterable[Fault] = (),
                 wave_timeout: Optional[float] = None) -> None:
        if engine not in ("compiled", "reference"):
            raise ValueError(f"unknown engine {engine!r}")
        check_topology(topology)
        self.topology = topology
        self.policy: Policy = HVLB_CC_B() if policy is None else policy
        self.engine = engine
        self.backend = backend
        self.batch = validate_batch(batch)
        # active resource faults: start from ``faults`` (so a restarted
        # service can resume a degraded fleet), grown/shrunk by
        # mark_failed/degrade/restore.  ComputeSpike is graph drift, not
        # resource state — FaultSpec.from_faults rejects it here.
        self._spec = FaultSpec.from_faults(faults, topology)
        # engine watchdog: per-wave wall-clock budget (seconds) applied to
        # *device* backends only — a wave overrun raises WaveTimeoutError,
        # which the fallback chain demotes on.  None (default, or env
        # REPRO_SCHED_WAVE_TIMEOUT unset/empty) disables the watchdog.
        if wave_timeout is None:
            env = os.environ.get("REPRO_SCHED_WAVE_TIMEOUT", "")
            wave_timeout = float(env) if env else None
        if wave_timeout is not None and wave_timeout <= 0:
            raise ValueError(f"wave_timeout must be > 0 seconds, got "
                             f"{wave_timeout!r}")
        self.wave_timeout = wave_timeout
        self._sessions: Dict[int, _GraphSession] = {}
        self._last: Optional[_GraphSession] = None
        # probe_update's dry-run state, reused by a matching update()
        self._probe: Optional[tuple] = None

    def _resolve_batch(self, batch: Optional[int]) -> Optional[int]:
        """Concrete level-batch cap for this call (None for reference —
        the readable reference walks one decision at a time).

        The value is validated (``engine.validate_batch``, the single
        source of truth) even under the reference engine, so an invalid
        ``batch=`` fails loudly instead of being silently ignored until
        the session switches to the compiled engine.
        """
        b = self.batch if batch is None else validate_batch(batch)
        if self.engine != "compiled":
            return None
        return DEFAULT_BATCH_MAX if b is None else b

    def _resolve_backend(self, backend: Optional[str]) -> Optional[str]:
        """Concrete evaluator name for this call (None for reference).

        The name is validated even under the reference engine, so a
        typo'd ``backend=`` fails loudly instead of being silently
        ignored until the session switches to the compiled engine.
        """
        name = resolve_backend_name(
            self.backend if backend is None else backend,
            self.topology.n_procs, self.topology)
        return name if self.engine == "compiled" else None

    def _resolve_backend_fb(self, backend: Optional[str]
                            ) -> Tuple[Optional[str],
                                       Tuple[Tuple[str, str, str], ...]]:
        """Resolve with the fallback chain's resolve-time demotion.

        A requested *device* backend that cannot even resolve (jax
        missing / broken install) demotes to the chain's next NumPy
        backend instead of raising — the session must survive a broken
        opt-in accelerator — and the pending ``(from, to, reason)``
        record is attached to the produced plan.  Everything else
        (unknown names, vector-incompatibility) raises exactly like
        :meth:`_resolve_backend`.
        """
        req = self.backend if backend is None else backend
        if req is None:
            req = default_backend()
        try:
            return self._resolve_backend(req), ()
        except Exception as e:
            if req not in _DEVICE_BACKENDS:
                raise
            target = self._fallback_chain(req)[1]
            _warn_fallback(req, target, e)
            return (target if self.engine == "compiled" else None,
                    ((req, target, f"{type(e).__name__}: {e}"),))

    def _fallback_chain(self, name: Optional[str]) -> List[str]:
        """Demotion order starting at ``name`` (device backends only
        grow a tail: pallas -> vector (when route-compatible) -> scalar)."""
        chain = [name]
        if name in _DEVICE_BACKENDS:
            if vector_compatible(self.topology):
                chain.append("vector")
            chain.append("scalar")
        return chain

    # ------------------------------------------------------------- submit
    @tracing.traced("repro.api.submit")
    def submit(self, g: SPG, policy: Optional[Policy] = None,
               backend: Optional[str] = None,
               batch: Optional[int] = None) -> Plan:
        """Compile (once) and schedule ``g`` under ``policy``.

        Re-submitting the same graph object reuses its compiled instance,
        priority queues, and — for an unchanged (policy, backend, batch)
        — the cached plan.
        """
        policy = self.policy if policy is None else policy
        bname, pending = self._resolve_backend_fb(backend)
        bcap = self._resolve_batch(batch)
        sess = self._sessions.get(id(g))
        if sess is None or sess.g is not g:
            with tracing.span("repro.api.prepare"):
                check_graph(g)       # actionable errors at the boundary
                sess = _GraphSession(g, self.topology,
                                     compiled=self.engine == "compiled",
                                     faults=self._spec)
            self._sessions[id(g)] = sess
        self._last = sess
        plan = sess.plans.get((policy, bname, bcap))
        if plan is None:
            plan = self._plan_fb(sess, policy, backend=bname, batch=bcap,
                                 pending=pending)
            sess.plans[(policy, bname, bcap)] = plan
        return plan

    @tracing.traced("repro.api.submit_many")
    def submit_many(self, graphs: Iterable[SPG],
                    policy: Optional[Policy] = None,
                    backend: Optional[str] = None,
                    batch: Optional[int] = None) -> FleetPlan:
        """Schedule several independent SPGs against shared link state in
        one engine pass (the exp6 fleet scenario).

        The graphs are joined into one disjoint-union SPG; the merged
        priority queue is the stable merge of the per-graph queues (the
        global HPRV sort restricted to one graph's nodes reproduces that
        graph's own queue), so precedence safety per graph is preserved.
        The union session stays cached: a later ``update(task_rates=...)``
        (keyed by union node ids) replays the fleet schedule
        incrementally.
        """
        graphs = list(graphs)
        if not graphs:
            raise ValueError("submit_many needs at least one graph")
        policy = self.policy if policy is None else policy
        union, offsets = _disjoint_union(graphs, self.topology)
        plan = self.submit(union, policy, backend=backend, batch=batch)
        return FleetPlan(schedule=plan.schedule, graphs=graphs,
                         offsets=offsets, policy=policy,
                         period=plan.period, sweep=plan.sweep,
                         backend=plan.backend, batch=plan.batch,
                         fallback=plan.fallback)

    # ------------------------------------------------------------- update
    @tracing.traced("repro.api.probe_update")
    def probe_update(self, *, task_rates: Dict[int, float],
                     graph: Optional[SPG] = None,
                     policy: Optional[Policy] = None) -> int:
        """Dry-run of ``update(task_rates=...)``: how many leading
        decisions of the memoized trace provably survive the drift.

        Costs one vectorized rank/LDET recomputation — no scheduling.
        ``n`` (every decision survives — the drift is invisible to this
        policy) down to ``0`` (full re-simulation).  A matching
        ``update()`` right after reuses the probe's prepared state, so
        probing before updating costs nothing extra.
        """
        policy = self.policy if policy is None else policy
        sess = self._session_of(graph)
        if sess is None:
            raise ValueError("probe_update() before any submit()")
        check_task_rates(task_rates, sess.g.n)
        changed = {t: f for t, f in task_rates.items() if f != 1.0}
        queue_len = len(sess.queue_for(self.topology, policy))
        if not changed:
            return queue_len
        if self.engine != "compiled":
            return 0
        with tracing.span("repro.api.prepare"):
            new_sess = _GraphSession(_rescaled_graph(sess.g, [changed]),
                                     self.topology, compiled=True,
                                     faults=self._spec)
        prefix = self._clean_prefix(sess, new_sess, policy)
        self._probe = (sess, policy, tuple(sorted(changed.items())),
                       new_sess, prefix)
        return prefix

    @tracing.traced("repro.api.update")
    def update(self, *,
               task_rates: Union[Dict[int, float],
                                 Sequence[Dict[int, float]], None] = None,
               link_speed: Union[Dict[str, float],
                                 Sequence[Dict[str, float]], None] = None,
               graph: Optional[SPG] = None,
               policy: Optional[Policy] = None,
               backend: Optional[str] = None,
               batch: Optional[int] = None) -> Plan:
        """Re-plan after drift, replaying only the affected trace suffix.

        ``task_rates`` maps task -> arrival-rate factor on its
        computational volume; ``link_speed`` overrides named link speeds
        of the session topology (which invalidates every cached instance
        — LDET and all message timings change, so the whole trace is
        re-simulated).  Both accept either one dict or a *sequence* of
        dicts — a batch of pending perturbation events, oldest first —
        in which case the k events are folded into ONE combined suffix
        replay (task factors compose sequentially, later link-speed
        overrides win) whose result is bit-identical to applying the
        events through k separate ``update()`` calls;
        ``ReplayStats.coalesced`` records the fold.  This is the
        coalescing primitive of the serving layer (``repro.service``).
        ``graph`` selects which submitted graph to update (default: the
        most recently submitted).  The returned plan is bit-identical to
        a from-scratch ``submit`` of the modified graph under the same
        pinned period (``Plan.period``).
        """
        policy = self.policy if policy is None else policy
        sess = self._session_of(graph)
        if sess is None:
            raise ValueError("update() before any submit(): the session "
                             "has no graph to re-plan")
        tr_events = _as_events(task_rates)
        ls_events = [ev for ev in _as_events(link_speed) if ev]
        for ev in tr_events:
            check_task_rates(ev, sess.g.n)
        for ev in ls_events:
            check_link_speeds(ev, self.topology)
        changed_events = [ce for ce in
                          ({t: f for t, f in ev.items() if f != 1.0}
                           for ev in tr_events) if ce]
        link_changed = bool(ls_events)
        n_events = len(changed_events) + len(ls_events)

        if link_changed:
            speeds = dict(self.topology.link_speed)
            for ev in ls_events:
                speeds.update(ev)
            self.topology = Topology(
                list(self.topology.proc_names), self.topology.rates.copy(),
                speeds, {pair: list(rr)
                         for pair, rr in self.topology.routes.items()},
                ctml_mode=self.topology.ctml_mode)
            # every compiled instance embeds the old link speeds
            self._sessions = {}

        if not changed_events and not link_changed:
            self._sessions[id(sess.g)] = sess
            self._last = sess
            return self.submit(sess.g, policy, backend=backend, batch=batch)

        probe = self._probe
        self._probe = None
        if probe is not None and not link_changed \
                and len(changed_events) == 1 and probe[:3] == (
                    sess, policy, tuple(sorted(changed_events[0].items()))):
            new_sess, suffix_start = probe[3], probe[4]
            new_g = new_sess.g
        else:
            new_g = _rescaled_graph(sess.g, changed_events) \
                if changed_events else sess.g
            with tracing.span("repro.api.prepare"):
                new_sess = _GraphSession(new_g, self.topology,
                                         compiled=self.engine == "compiled",
                                         faults=self._spec)
            suffix_start = 0
            if self.engine == "compiled" and not link_changed:
                suffix_start = self._clean_prefix(sess, new_sess, policy)
        new_sess.periods = dict(sess.periods)    # keep the pinned period

        prev_traces: Optional[Dict[float, DecisionTrace]] = None
        if suffix_start > 0:
            prev_traces = sess.traces.get(policy)

        bname, pending = self._resolve_backend_fb(backend)
        bcap = self._resolve_batch(batch)
        plan = self._plan_fb(new_sess, policy, prev_traces=prev_traces,
                             suffix_start=suffix_start, backend=bname,
                             batch=bcap, pending=pending)
        plan.replay.coalesced = max(1, n_events)
        new_sess.plans[(policy, bname, bcap)] = plan
        # the originally submitted handle and the new graph both address
        # this session; every map entry still pointing at the superseded
        # session is evicted (else each update would leak one session)
        new_sess.handles = [sess.handles[0], new_g]
        self._sessions = {k: v for k, v in self._sessions.items()
                          if v is not sess}
        for h in new_sess.handles:
            self._sessions[id(h)] = new_sess
        self._last = new_sess
        return plan

    # ------------------------------------------------------------- faults
    @property
    def faults(self) -> FaultSpec:
        """The active resource-fault spec (empty when healthy)."""
        return self._spec

    @tracing.traced("repro.api.mark_failed")
    def mark_failed(self, *, proc: Optional[int] = None,
                    link: Optional[str] = None,
                    graph: Optional[SPG] = None,
                    policy: Optional[Policy] = None,
                    backend: Optional[str] = None,
                    batch: Optional[int] = None) -> Optional[Plan]:
        """Record a hard resource failure and replan around it.

        Exactly one of ``proc`` (processor index — :class:`ProcessorDown`)
        or ``link`` (link name — :class:`LinkDown`) must be given.  The
        replan invalidates exactly the decision-trace suffix that touches
        the failed resource: for a processor, positions from its first
        placement; for a link, positions from the first committed message
        interval on it (everything earlier is provably unchanged — the
        priorities stay healthy and a masked resource only worsens losing
        candidates, see DESIGN.md §6).  ``ReplayStats.invalidated_by_fault``
        on the returned plan counts the invalidated positions.

        Raises :class:`InfeasibleScheduleError` when some task has no
        feasible placement left; the fault stays recorded either way.
        Returns ``None`` when called before any ``submit`` (the fault is
        recorded and applies to every later submit).
        """
        if (proc is None) == (link is None):
            raise ValueError("mark_failed needs exactly one of "
                             "proc=<index> or link=<name>")
        fault: Fault = ProcessorDown(int(proc)) if proc is not None \
            else LinkDown(link)
        return self._apply_fault(fault, graph, policy, backend, batch)

    @tracing.traced("repro.api.degrade")
    def degrade(self, *, link: Optional[str] = None,
                task: Optional[int] = None, factor: float,
                graph: Optional[SPG] = None,
                policy: Optional[Policy] = None,
                backend: Optional[str] = None,
                batch: Optional[int] = None) -> Optional[Plan]:
        """Record a soft degradation and replan.

        ``link=`` sets the link's slowdown factor (CTML of every message
        on it scales by ``factor``; ``factor=1`` restores nominal speed).
        ``task=`` is a :class:`ComputeSpike`: the task's computational
        volume scales by ``factor`` via the ``update(task_rates=...)``
        drift machinery (it rescales the *current* graph, so two spikes
        of 2.0 compose to 4.0).  Suffix invalidation follows the same
        trace-scan rule as :meth:`mark_failed`; a degradation that makes
        a link *faster* than before (factor below the previous one)
        conservatively invalidates the whole trace.
        """
        if (link is None) == (task is None):
            raise ValueError("degrade needs exactly one of link=<name> "
                             "or task=<index>")
        if task is not None:
            plan = self.update(task_rates={int(task): float(factor)},
                               graph=graph, policy=policy, backend=backend,
                               batch=batch)
            plan.replay.invalidated_by_fault = \
                plan.graph.n - plan.replay.suffix_start
            return plan
        return self._apply_fault(LinkDegraded(link, float(factor)),
                                 graph, policy, backend, batch)

    @tracing.traced("repro.api.restore")
    def restore(self, *, proc: Optional[int] = None,
                link: Optional[str] = None,
                graph: Optional[SPG] = None,
                policy: Optional[Policy] = None,
                backend: Optional[str] = None,
                batch: Optional[int] = None) -> Optional[Plan]:
        """Clear a recorded fault and replan (full re-simulation: a
        restored resource can improve *any* decision, so no prefix is
        provably unchanged).  No-op replan if the resource was healthy."""
        if (proc is None) == (link is None):
            raise ValueError("restore needs exactly one of proc=<index> "
                             "or link=<name>")
        new_spec = self._spec.without(proc=proc, link=link)
        return self._fault_event(new_spec, None, graph, policy, backend,
                                 batch)

    def _apply_fault(self, fault: Fault, graph: Optional[SPG],
                     policy: Optional[Policy], backend: Optional[str],
                     batch: Optional[int]) -> Optional[Plan]:
        new_spec = self._spec.with_fault(fault, self.topology)
        scan: Optional[tuple] = None
        if isinstance(fault, ProcessorDown):
            scan = ("proc", fault.proc)
        else:                    # LinkDown / LinkDegraded
            old_f = self._spec.link_factor(fault.link)
            new_f = new_spec.link_factor(fault.link)
            if new_f >= old_f:
                # strictly-worse (or unchanged) link: the trace prefix
                # whose committed messages avoid it is provably unchanged
                scan = ("link", self.topology.link_index()[fault.link])
            # a *faster* link can improve any decision: scan stays None
            # (conservative full invalidation)
        return self._fault_event(new_spec, scan, graph, policy, backend,
                                 batch)

    def _fault_event(self, new_spec: FaultSpec, scan: Optional[tuple],
                     graph: Optional[SPG], policy: Optional[Policy],
                     backend: Optional[str], batch: Optional[int]
                     ) -> Optional[Plan]:
        policy = self.policy if policy is None else policy
        sess = self._session_of(graph)
        self._spec = new_spec
        # every cached session embeds the previous spec's masking
        self._sessions = {}
        self._probe = None
        if sess is None:
            self._last = None
            return None          # recorded; applies to every later submit
        queue = sess.queue_for(self.topology, policy)
        suffix_start = 0
        if self.engine == "compiled" and scan is not None:
            traces = sess.traces.get(policy)
            if traces:
                suffix_start = min(
                    self._fault_prefix(tr, scan) for tr in traces.values())
        prev_traces = sess.traces.get(policy) if suffix_start > 0 else None
        with tracing.span("repro.api.prepare"):
            new_sess = _GraphSession(sess.g, self.topology,
                                     compiled=self.engine == "compiled",
                                     faults=new_spec,
                                     rank=sess.rank, ldet=sess.ldet)
        new_sess.queues = dict(sess.queues)      # healthy heuristics
        new_sess.periods = dict(sess.periods)    # keep the pinned period
        bname, pending = self._resolve_backend_fb(backend)
        bcap = self._resolve_batch(batch)
        try:
            plan = self._plan_fb(new_sess, policy, prev_traces=prev_traces,
                                 suffix_start=suffix_start, backend=bname,
                                 batch=bcap, pending=pending,
                                 invalidated=len(queue) - suffix_start)
        except InfeasibleScheduleError:
            # the fault stays recorded and the stale sessions stay
            # dropped: later submits keep raising until restore()
            self._last = None
            raise
        new_sess.plans[(policy, bname, bcap)] = plan
        new_sess.handles = list(sess.handles)
        for h in new_sess.handles:
            self._sessions[id(h)] = new_sess
        self._last = new_sess
        return plan

    @staticmethod
    def _fault_prefix(trace: DecisionTrace, scan: tuple) -> int:
        """First trace position touching the failed resource (trace
        length when none does — the whole trace survives)."""
        kind, ident = scan
        if kind == "proc":
            for k, rec in enumerate(trace.records):
                if rec[1] == ident:
                    return k
        else:
            for k, rec in enumerate(trace.records):
                for (_i, _route, iv) in rec[4]:
                    for (lid, _s, _f) in iv:
                        if lid == ident:
                            return k
        return len(trace.records)

    def _session_of(self, graph: Optional[SPG]) -> Optional[_GraphSession]:
        if graph is None:
            return self._last
        sess = self._sessions.get(id(graph))
        # identity check guards against id() reuse after a submitted graph
        # handle was garbage-collected
        if sess is not None and not any(h is graph for h in sess.handles):
            return None
        return sess

    def _clean_prefix(self, old: _GraphSession, new: _GraphSession,
                      policy: Policy) -> int:
        """First queue position whose decision the drift can reach.

        A position's decision (and its committed floats) depends only on
        the task's comp/LDET rows, its inbound message volumes, the
        shared period, and the state left by earlier positions.  Rows are
        compared exactly (vectorized recomputation is deterministic), so
        any position before the first affected one is provably unchanged
        and can be re-committed from the memoized trace.
        """
        tg = self.topology
        old_q = old.queue_for(tg, policy)
        new_q = new.queue_for(tg, policy)
        prefix = 0
        for a, b in zip(old_q, new_q):
            if a != b:
                break
            prefix += 1
        comp_old = old.g.comp_matrix_for(tg.rates)
        comp_new = new.g.comp_matrix_for(tg.rates)
        comp_diff = np.any(comp_old != comp_new, axis=1)
        row_diff = comp_diff | np.any(old.ldet != new.ldet, axis=1)
        affected = set(np.flatnonzero(row_diff).tolist())
        if new.g.tpl_proportional_ccr is not None:
            # tpl(e_ij | p) = CCR * comp(i, p): successors' inbound
            # message volumes changed with the source's comp row
            for i in np.flatnonzero(comp_diff).tolist():
                affected.update(new.g.succ[i])
        if affected:
            pos = {t: k for k, t in enumerate(new_q)}
            prefix = min(prefix, min(pos[t] for t in affected))
        return prefix

    # -------------------------------------------------------------- plan
    def _plan_fb(self, sess: _GraphSession, policy: Policy,
                 prev_traces: Optional[Dict[float, DecisionTrace]] = None,
                 suffix_start: int = 0,
                 backend: Optional[str] = None,
                 batch: Optional[int] = None,
                 pending: Tuple[Tuple[str, str, str], ...] = (),
                 invalidated: int = 0) -> Plan:
        """Run :meth:`_plan` under the backend fallback chain.

        A *device* backend (pallas) failing with a compile/runtime error
        or a :class:`~.faults.WaveTimeoutError` demotes to the next
        backend in :meth:`_fallback_chain` for this plan — decisions are
        backend-identical, so the demoted plan's schedule is the one the
        requested backend would have produced.  Semantic scheduler errors
        (:class:`~.faults.InfeasibleScheduleError`,
        :class:`~.scheduler.SchedulingFailure`) always propagate: they
        would reproduce on any backend.  Each demotion is recorded on
        ``Plan.fallback`` and warned once per process; ``pending``
        carries demotions already taken at backend-resolve time.
        """
        chain = self._fallback_chain(backend)
        records = list(pending)
        for k, name in enumerate(chain):
            inst = sess.inst
            device = name in _DEVICE_BACKENDS
            if inst is not None and device:
                inst.wave_timeout = self.wave_timeout
            try:
                plan = self._plan(sess, policy, prev_traces=prev_traces,
                                  suffix_start=suffix_start, backend=name,
                                  batch=batch, invalidated=invalidated)
            except (InfeasibleScheduleError, SchedulingFailure):
                raise
            except Exception as e:
                if not device or k + 1 >= len(chain):
                    raise
                records.append((name, chain[k + 1],
                                f"{type(e).__name__}: {e}"))
                _warn_fallback(name, chain[k + 1], e)
                continue
            finally:
                if inst is not None:
                    inst.wave_timeout = None
            if records:
                plan.fallback = tuple(records)
            return plan
        raise AssertionError("unreachable: fallback chain exhausted")

    def _plan(self, sess: _GraphSession, policy: Policy,
              prev_traces: Optional[Dict[float, DecisionTrace]] = None,
              suffix_start: int = 0,
              backend: Optional[str] = None,
              batch: Optional[int] = None,
              invalidated: int = 0) -> Plan:
        g = sess.g
        queue = sess.queue_for(self.topology, policy)
        inst = sess.inst
        sim0 = inst.n_decisions_simulated if inst is not None else 0
        rep0 = inst.n_decisions_replayed if inst is not None else 0
        sims_resumed = sims_full = 0

        if isinstance(policy, HSV_CC):
            # alpha = 0 makes the period irrelevant to the schedule, but it
            # is pinned anyway so resumed traces stay self-consistent
            period = sess.periods.get(policy)
            if period is None:
                period = sess.default_period(self.topology)
                sess.periods[policy] = period
            if inst is None:
                best = list_schedule(g, self.topology, queue, sess.rank,
                                     alpha=0.0, ldet=sess.ldet)
                sims_full = 1
                sweep = None
            else:
                prev = (prev_traces or {}).get(0.0)
                pos = suffix_start if prev is not None else 0
                best, _, tr = inst.schedule_traced(
                    queue, 0.0, period=period, want_bound=False,
                    resume=prev, resume_pos=pos, backend=backend,
                    batch=batch)
                sess.traces[policy] = {0.0: tr}
                sims_resumed, sims_full = (1, 0) if pos else (0, 1)
                sweep = None
        else:
            if policy.sweep not in ("grid", "adaptive"):
                raise ValueError(f"unknown sweep {policy.sweep!r}")
            if inst is None and policy.sweep != "grid":
                raise ValueError("sweep='adaptive' requires "
                                 "engine='compiled'")
            period = sess.periods.get(policy)
            if period is None:
                period = policy.period if policy.period is not None \
                    else sess.default_period(self.topology)
                sess.periods[policy] = period
            if inst is None:
                sweep = self._sweep_reference(sess, queue, policy, period)
                sims_full = len(sweep.alphas)
            else:
                traces: Dict[float, DecisionTrace] = {}
                sweep, sims_resumed, sims_full = self._sweep_compiled(
                    inst, queue, policy, period, traces,
                    prev_traces, suffix_start, backend, batch)
                sess.traces[policy] = traces
            best = sweep.best

        replay = ReplayStats(
            suffix_start=suffix_start,
            decisions_simulated=(inst.n_decisions_simulated - sim0)
            if inst is not None else sims_full * g.n,
            decisions_replayed=(inst.n_decisions_replayed - rep0)
            if inst is not None else 0,
            sims_resumed=sims_resumed, sims_full=sims_full,
            invalidated_by_fault=invalidated)
        holes = schedule_holes(best, include_unbounded=True) \
            if isinstance(policy, HVLB_CC_IC) else None
        return Plan(schedule=best, policy=policy, graph=g, period=period,
                    sweep=sweep, holes=holes, replay=replay,
                    backend=backend, batch=batch)

    # ------------------------------------------------------------- sweeps
    def _sweep_compiled(self, inst: CompiledInstance, queue: Sequence[int],
                        policy: HVLB_CC_A, period: float,
                        traces: Dict[float, DecisionTrace],
                        prev_traces: Optional[Dict[float, DecisionTrace]],
                        suffix_start: int,
                        backend: Optional[str] = None,
                        batch: Optional[int] = None
                        ) -> Tuple[SweepResult, int, int]:
        n_steps = int(round(policy.alpha_max / policy.alpha_step))
        counters = [0, 0]                      # [resumed, full]

        if policy.sweep == "grid" and n_steps == 0:
            # single-point grid (the online re-plan unit): no rival alphas
            # to bound against, so skip the per-decision crossing tracking.
            # The schedule floats are unaffected by bound tracking, and the
            # grid shape is a pure function of the policy, so resume traces
            # stay consistent across updates.
            prev = (prev_traces or {}).get(0.0)
            pos = suffix_start if prev is not None else 0
            s, _, tr = inst.schedule_traced(queue, 0.0, period=period,
                                            want_bound=False,
                                            resume=prev, resume_pos=pos,
                                            backend=backend, batch=batch)
            traces[0.0] = tr
            return (SweepResult.from_points(s, 0.0, [(0.0, s.makespan)]),
                    1 if pos else 0, 0 if pos else 1)

        if policy.sweep == "grid" and not (prev_traces and suffix_start) \
                and inst.sweep_supported(backend):
            # (A, B) fused sweep (DESIGN.md §5): every grid alpha's whole
            # schedule in ONE device dispatch.  Fresh grids only — a
            # resumable update goes through the host loop below, which
            # replays per-alpha trace prefixes.  Selection matches the
            # host loop exactly: trace-invariance means the alphas the
            # host loop would have skipped produce bit-equal makespans
            # here, and the same strict-improvement rule scans them in
            # the same order.  Only alpha*'s schedule is built; every
            # other alpha's trace builds when an update or a fault
            # replan first reads it.
            alphas = [k * policy.alpha_step for k in range(n_steps + 1)]
            swept = inst.schedule_sweep(queue, alphas, period=period,
                                        backend=backend, batch=batch)
            makespans = swept.makespans.tolist()
            k_best = 0
            for k in range(1, len(alphas)):
                # analysis: allow[float-arith] strict-improvement epsilon on a reduction over backend outputs, not a per-decision value
                if makespans[k] < makespans[k_best] - 1e-12:
                    k_best = k
            for k, alpha in enumerate(alphas):
                traces[alpha] = swept.trace(k)
            return (SweepResult.from_points(swept[k_best][0], alphas[k_best],
                                            list(zip(alphas, makespans))),
                    0, len(alphas))

        def grid_pass(alphas: Sequence[float], points, best, best_alpha):
            k = 0
            while k < len(alphas):
                alpha = alphas[k]
                prev = (prev_traces or {}).get(alpha)
                pos = suffix_start if prev is not None else 0
                counters[0 if pos else 1] += 1
                s, bnd, tr = inst.schedule_traced(
                    queue, alpha, period=period, want_bound=True,
                    resume=prev, resume_pos=pos, backend=backend,
                    batch=batch)
                traces[alpha] = tr
                points.append((alpha, s.makespan))
                # analysis: allow[float-arith] strict-improvement epsilon on a reduction over backend outputs, not a per-decision value
                if best is None or s.makespan < best.makespan - 1e-12:
                    best, best_alpha = s, alpha
                k += 1
                # identical decision trace => identical schedule
                # analysis: allow[float-arith] trace-invariance skip bound; margin only widens the re-evaluated alpha set, never changes a schedule
                while k < len(alphas) and alphas[k] < bnd - _SKIP_MARGIN:
                    points.append((alphas[k], s.makespan))
                    k += 1
            return best, best_alpha

        points: List[Tuple[float, float]] = []
        if policy.sweep == "grid":
            alphas = [k * policy.alpha_step for k in range(n_steps + 1)]
            best, best_alpha = grid_pass(alphas, points, None, 0.0)
        else:                                  # adaptive coarse-to-fine
            step, cf = policy.alpha_step, max(1, policy.coarse_factor)
            coarse = [k * step for k in range(0, n_steps + 1, cf)]
            if coarse[-1] != n_steps * step:
                coarse.append(n_steps * step)
            best, best_alpha = grid_pass(coarse, points, None, 0.0)
            assert best is not None
            # refine around every coarse point within the policy's band
            cutoff = best.makespan * policy.refine_within
            refine: set = set()
            for a, m in points:
                if m <= cutoff:
                    ka = int(round(a / step))
                    refine.update(range(max(0, ka - cf),
                                        min(n_steps, ka + cf) + 1))
            done = {round(a, 12) for a, _ in points}
            fine = [k * step for k in sorted(refine)
                    if round(k * step, 12) not in done]
            best, best_alpha = grid_pass(fine, points, best, best_alpha)
            points.sort()
        assert best is not None
        return (SweepResult.from_points(best, best_alpha, points),
                counters[0], counters[1])

    def _sweep_reference(self, sess: _GraphSession, queue: Sequence[int],
                         policy: HVLB_CC_A, period: float) -> SweepResult:
        g, tg = sess.g, self.topology
        n_steps = int(round(policy.alpha_max / policy.alpha_step))
        best: Optional[Schedule] = None
        best_alpha = 0.0
        points: List[Tuple[float, float]] = []
        for k in range(n_steps + 1):
            alpha = k * policy.alpha_step
            s = list_schedule(g, tg, queue, sess.rank, alpha=alpha,
                              period=period, ldet=sess.ldet)
            points.append((alpha, s.makespan))
            # analysis: allow[float-arith] same strict-improvement epsilon as the session sweep (deprecated shim must stay bit-identical)
            if best is None or s.makespan < best.makespan - 1e-12:
                best, best_alpha = s, alpha
        assert best is not None
        return SweepResult.from_points(best, best_alpha, points)
