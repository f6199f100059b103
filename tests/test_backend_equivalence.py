"""Scalar vs vector vs pallas candidate-evaluation backends.

The vector backend re-expresses the engine's per-processor candidate
loop as (P,)-batch array ops, reassociating only exact operations
(IEEE max), so its schedules — start/finish floats, message routes,
per-link intervals, alpha-sweep curves, crossing bounds, IC holes, and
decision-replay counters — must equal the scalar backend's exactly.
No tolerance in the scalar/vector half of this file.

The Pallas backend (interpret mode on CPU runners) performs the same
float64 arithmetic inside a device kernel; its contract is *decision
identity* — same winner tuples, hence same processor assignments,
routes, and replay counters — with makespans/floats equal within float
tolerance (in practice they come out bit-identical on the interpret
path, but only decision identity is pinned; see DESIGN §5).

Covered: the paper worked example (multi-route topology, CTML
quantization), the 200-graph mixed-config corpus, wide single-route
topologies (P = 8, 16 — where "auto" actually picks vector), all four
policies including HVLB_CC_IC schedule holes / precision, and
``Scheduler.update`` trace replay across backends (traces are
backend-portable).
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (HSV_CC, HVLB_CC_A, HVLB_CC_B, HVLB_CC_IC,
                        CompiledInstance, Scheduler, paper_spg,
                        paper_topology, random_spg, resolve_backend_name)
from repro.core.backends import AUTO_VECTOR_MIN_P, BackendCompatError
from repro.core.engine import DEFAULT_BATCH_MAX
from repro.core.backends.vector import VectorBackend
from repro.core.ranks import hprv_b, priority_queue, rank_matrix
from repro.core.topology import Topology, fully_switched_topology

RATE_PATTERNS = [(1.0, 0.67, 0.83), (0.83, 0.67, 1.0), (0.67, 0.83, 1.0)]

POLICIES = [
    HSV_CC(),
    HVLB_CC_A(alpha_max=1.0, alpha_step=0.25, period=150.0),
    HVLB_CC_B(alpha_max=1.0, alpha_step=0.25, period=150.0),
    HVLB_CC_IC(alpha_max=1.0, alpha_step=0.25, period=150.0),
]


def assert_identical(a, b):
    assert np.array_equal(a.proc, b.proc)
    assert np.array_equal(a.start, b.start)        # exact, no tolerance
    assert np.array_equal(a.finish, b.finish)
    assert set(a.messages) == set(b.messages)
    for e, ma in a.messages.items():
        mb = b.messages[e]
        assert ma.route == mb.route
        assert ma.intervals == mb.intervals        # exact floats
        assert (ma.src_proc, ma.dst_proc) == (mb.src_proc, mb.dst_proc)


def assert_plans_identical(pa, pb):
    assert_identical(pa.schedule, pb.schedule)
    assert pa.period == pb.period
    if pa.sweep is not None:
        assert np.array_equal(pa.sweep.alphas, pb.sweep.alphas)
        assert np.array_equal(pa.sweep.makespans, pb.sweep.makespans)
        assert pa.sweep.best_alpha == pb.sweep.best_alpha
    if pa.holes is not None:
        assert pa.holes == pb.holes                # exact, inf included


def _case(seed: int):
    """Same mixed-config generator as tests/test_engine_equivalence.py."""
    rng = np.random.default_rng(seed)
    rates = RATE_PATTERNS[seed % 3]
    tg = paper_topology(rates=rates)
    ccr = [0.1, 1.0, 10.0][(seed // 3) % 3]
    constrained = (seed // 9) % 2 == 0
    n = int(rng.integers(8, 31))
    g = random_spg(n, rng, ccr=ccr, tg=tg, outdeg_constraint=constrained)
    return g, tg


def _wide(P: int, seed: int, n: int = 28):
    rng = np.random.default_rng(seed)
    tg = fully_switched_topology(P, rates=rng.uniform(0.6, 1.2, size=P),
                                 link_speeds=rng.uniform(0.5, 3.0, size=P))
    g = random_spg(n, rng, ccr=1.0, tg=tg, max_in=3, max_out=6)
    return g, tg


# ---------------------------------------------------------------- paper
@pytest.mark.parametrize("policy", POLICIES,
                         ids=lambda p: type(p).__name__)
def test_paper_example_policies_backend_identical(policy):
    g, tg = paper_spg(), paper_topology()
    pa = Scheduler(tg, backend="scalar").submit(g, policy)
    pb = Scheduler(tg, backend="vector").submit(g, policy)
    assert pa.backend == "scalar" and pb.backend == "vector"
    assert_plans_identical(pa, pb)
    if isinstance(policy, HVLB_CC_IC):
        # unbounded exit holes and degradation curves match exactly
        assert any(np.isinf(h) for h in pa.holes.values())
        for t in pa.holes:
            for lam in (0.5, 2.0, 100.0):
                assert pa.precision(t, lam) == pb.precision(t, lam)


# ------------------------------------------------------------- corpus
@pytest.mark.parametrize("seed", range(200))
def test_backend_equivalence_random(seed):
    """Bit-identical single passes and crossing bounds on the 200-graph
    corpus (paper-style multi-route topology, both backends sharing one
    compiled instance)."""
    g, tg = _case(seed)
    r = rank_matrix(g, tg)
    q = priority_queue(hprv_b(g, tg, r), r.mean(1))
    inst = CompiledInstance(g, tg, rank=r)
    for alpha in (0.0, 0.85):
        s = inst.schedule(q, alpha=alpha, backend="scalar")
        v = inst.schedule(q, alpha=alpha, backend="vector")
        assert_identical(s, v)
        sb, bs = inst.schedule_with_bound(q, alpha, backend="scalar")
        vb, bv = inst.schedule_with_bound(q, alpha, backend="vector")
        assert_identical(sb, vb)
        assert bs == bv                            # exact bound float


@pytest.mark.parametrize("seed", range(0, 200, 13))
def test_policy_equivalence_random(seed):
    """All four policies produce identical plans under both backends on a
    corpus slice (sweeps, best schedules, IC holes).  Where a policy's
    HPRV_A queue cannot order an unconstrained graph (the Section-3.2
    failure mode), both backends must fail the same way."""
    from repro.core import SchedulingFailure

    g, tg = _case(seed)
    for policy in POLICIES:
        try:
            pa = Scheduler(tg, backend="scalar").submit(g, policy)
        except SchedulingFailure:
            with pytest.raises(SchedulingFailure):
                Scheduler(tg, backend="vector").submit(g, policy)
            continue
        pb = Scheduler(tg, backend="vector").submit(g, policy)
        assert_plans_identical(pa, pb)


@pytest.mark.parametrize("P", [8, 16])
@pytest.mark.parametrize("seed", [3, 17])
def test_backend_equivalence_wide_topology(P, seed):
    """Equivalence where auto-selection actually picks vector."""
    g, tg = _wide(P, seed)
    r = rank_matrix(g, tg)
    q = priority_queue(hprv_b(g, tg, r), r.mean(1))
    inst = CompiledInstance(g, tg, rank=r)
    for alpha in (0.0, 1.2):
        assert_identical(inst.schedule(q, alpha=alpha, backend="scalar"),
                         inst.schedule(q, alpha=alpha, backend="vector"))
        sb, bs = inst.schedule_with_bound(q, alpha, backend="scalar")
        vb, bv = inst.schedule_with_bound(q, alpha, backend="vector")
        assert_identical(sb, vb)
        assert bs == bv
    pa = Scheduler(tg, backend="scalar").submit(
        g, HVLB_CC_B(alpha_max=1.0, alpha_step=0.25))
    pb = Scheduler(tg, backend="vector").submit(
        g, HVLB_CC_B(alpha_max=1.0, alpha_step=0.25))
    assert_plans_identical(pa, pb)


# ------------------------------------------------------- update replay
@pytest.mark.parametrize("seed,factor", [(0, 0.8), (2, 1.5), (5, 0.7)])
def test_update_replay_backend_identical(seed, factor):
    """update() replays identically under both backends: same suffix
    start, same replay counters, bit-identical plans."""
    rng = np.random.default_rng(seed)
    tg = paper_topology()
    g = random_spg(40, rng, ccr=1.0, tg=tg, outdeg_constraint=True)
    policy = HVLB_CC_B(alpha_max=1.0, alpha_step=0.5)
    plans = {}
    for backend in ("scalar", "vector"):
        sched = Scheduler(tg, policy=policy, backend=backend)
        plan = sched.submit(g)
        task = int(np.argmax(plan.schedule.start))
        plans[backend] = sched.update(task_rates={task: factor})
    ua, ub = plans["scalar"], plans["vector"]
    assert_plans_identical(ua, ub)
    assert dataclasses.asdict(ua.replay) == dataclasses.asdict(ub.replay)


def test_update_resumes_trace_recorded_by_other_backend():
    """Traces are backend-portable: a trace recorded under scalar replays
    bit-identically when the update runs under vector (and vice versa)."""
    rng = np.random.default_rng(11)
    tg = paper_topology()
    g = random_spg(40, rng, ccr=1.0, tg=tg, outdeg_constraint=True)
    policy = HVLB_CC_B(alpha_max=1.0, alpha_step=0.5)
    sched = Scheduler(tg, policy=policy)
    plan = sched.submit(g, backend="scalar")
    task = int(np.argmax(plan.schedule.start))
    upd = sched.update(task_rates={task: 0.8}, backend="vector")
    assert upd.backend == "vector"
    fresh = Scheduler(tg).submit(
        upd.graph, dataclasses.replace(policy, period=plan.period))
    assert_identical(upd.schedule, fresh.schedule)


# ------------------------------------------------------- auto-selection
ONE_POINT = HVLB_CC_B(alpha_max=0.0, alpha_step=0.5)   # orders any DAG


def test_auto_selection_by_processor_count(monkeypatch):
    # the CI matrix pins REPRO_SCHED_BACKEND; this test is about "auto"
    monkeypatch.delenv("REPRO_SCHED_BACKEND", raising=False)
    g3, tg3 = paper_spg(), paper_topology()
    assert Scheduler(tg3).submit(g3, ONE_POINT).backend == "scalar"
    g8, tg8 = _wide(AUTO_VECTOR_MIN_P, 5)
    assert Scheduler(tg8).submit(g8, ONE_POINT).backend == "vector"
    # per-call override beats the session default
    assert Scheduler(tg8, backend="scalar").submit(
        g8, ONE_POINT, backend="vector").backend == "vector"
    # reference engine has no numeric backend
    assert Scheduler(tg3, engine="reference").submit(
        g3, ONE_POINT).backend is None


def test_env_var_overrides_default_backend(monkeypatch):
    g, tg = paper_spg(), paper_topology()
    monkeypatch.setenv("REPRO_SCHED_BACKEND", "vector")
    plan = Scheduler(tg).submit(g, ONE_POINT)
    assert plan.backend == "vector"
    # explicit arguments still win over the environment
    assert Scheduler(tg, backend="scalar").submit(
        g, ONE_POINT).backend == "scalar"


def test_unknown_backend_rejected():
    g, tg = paper_spg(), paper_topology()
    with pytest.raises(ValueError, match="unknown backend"):
        Scheduler(tg, backend="cuda").submit(g, HSV_CC())


def _link_reuse_topology(P):
    loops = {(a, b): [tuple(f"l{a}" for _ in range(2))]
             for a in range(P) for b in range(a + 1, P)}
    return Topology([f"p{i}" for i in range(P)], np.ones(P),
                    {f"l{i}": 1.0 for i in range(P)}, loops)


def test_link_repeating_route_falls_back_to_scalar():
    """A route visiting a link twice is out of the vector backend's
    contract: auto falls back to scalar, explicit vector refuses — at
    resolve time and (defensively) at construction."""
    P = AUTO_VECTOR_MIN_P
    tg = _link_reuse_topology(P)
    assert resolve_backend_name("auto", P, tg) == "scalar"
    with pytest.raises(BackendCompatError, match="scalar"):
        resolve_backend_name("vector", P, tg)
    g = random_spg(10, np.random.default_rng(0), ccr=1.0, tg=tg)
    inst = CompiledInstance(g, tg)
    with pytest.raises(BackendCompatError, match="twice"):
        VectorBackend(inst)


def test_incompatible_backend_rejected_before_session_state():
    """An explicit vector request on a link-reuse topology fails at
    resolve time, inside submit(), *before* any session state exists:
    the plan/trace caches must not end up keyed for a plan that never
    materialized, and the session keeps working with a valid backend."""
    P = AUTO_VECTOR_MIN_P
    tg = _link_reuse_topology(P)
    g = random_spg(10, np.random.default_rng(0), ccr=1.0, tg=tg)
    sched = Scheduler(tg)
    with pytest.raises(BackendCompatError, match="use backend='scalar'"):
        sched.submit(g, HSV_CC(), backend="vector")
    assert sched._sessions == {}            # no half-built graph session
    with pytest.raises(BackendCompatError):
        Scheduler(tg, backend="vector").submit(g, HSV_CC())
    # a failed per-call override leaves the session fully usable and its
    # caches coherent: the scalar plan is fresh, not a stale leftover
    plan = sched.submit(g, HSV_CC(), backend="scalar")
    sess = sched._sessions[id(g)]
    assert set(sess.plans) == {(HSV_CC(), "scalar", DEFAULT_BATCH_MAX)}
    assert plan.backend == "scalar"
    assert plan.batch == DEFAULT_BATCH_MAX


# ------------------------------------------------ pallas (three-way)
PALLAS_POLICIES = [
    HSV_CC(),
    HVLB_CC_A(alpha_max=1.0, alpha_step=0.25, period=150.0),
    HVLB_CC_IC(alpha_max=1.0, alpha_step=0.25, period=150.0),
]


def assert_ran_pallas(plan):
    """The plan came from the device backend itself, not from a demotion
    down the fallback chain (which would hand back vector/scalar
    decisions that trivially match)."""
    assert plan.backend == "pallas"
    assert plan.fallback is None


def assert_decisions_identical(a, b):
    """Decision identity (the pallas contract): same winner tuples —
    processor assignments, message routes, replay-relevant structure —
    with start/finish/intervals equal within float tolerance."""
    assert np.array_equal(a.proc, b.proc)
    np.testing.assert_allclose(a.start, b.start, rtol=1e-9, atol=1e-9)
    np.testing.assert_allclose(a.finish, b.finish, rtol=1e-9, atol=1e-9)
    assert set(a.messages) == set(b.messages)
    for e, ma in a.messages.items():
        mb = b.messages[e]
        assert ma.route == mb.route
        assert (ma.src_proc, ma.dst_proc) == (mb.src_proc, mb.dst_proc)
        np.testing.assert_allclose(np.array([iv[1:] for iv in ma.intervals]),
                                   np.array([iv[1:] for iv in mb.intervals]),
                                   rtol=1e-9, atol=1e-9)
        assert [iv[0] for iv in ma.intervals] == \
            [iv[0] for iv in mb.intervals]


@pytest.mark.parametrize("policy", PALLAS_POLICIES,
                         ids=lambda p: type(p).__name__)
def test_paper_example_three_way(policy):
    """scalar / vector / pallas plans are decision-identical on the
    worked example for every policy class (multi-route topology, CTML
    quantization, IC holes + precision)."""
    pytest.importorskip("jax")
    g, tg = paper_spg(), paper_topology()
    plans = {b: Scheduler(tg, backend=b).submit(g, policy)
             for b in ("scalar", "vector", "pallas")}
    assert_ran_pallas(plans["pallas"])
    for b in ("vector", "pallas"):
        pa, pb = plans["scalar"], plans[b]
        assert_decisions_identical(pa.schedule, pb.schedule)
        assert pa.period == pb.period
        if pa.sweep is not None:
            assert np.array_equal(pa.sweep.alphas, pb.sweep.alphas)
            np.testing.assert_allclose(pa.sweep.makespans,
                                       pb.sweep.makespans, rtol=1e-9)
            assert pa.sweep.best_alpha == pb.sweep.best_alpha
        if pa.holes is not None:
            assert set(pa.holes) == set(pb.holes)
            for t, h in pa.holes.items():
                if np.isinf(h):
                    assert np.isinf(pb.holes[t])
                else:
                    assert pb.holes[t] == pytest.approx(h, rel=1e-9)
                for lam in (0.5, 2.0):
                    assert pb.precision(t, lam) == \
                        pytest.approx(pa.precision(t, lam), rel=1e-9)


@pytest.mark.parametrize("seed", range(0, 200, 29))
def test_three_way_equivalence_random(seed):
    """Corpus slice: single passes and crossing bounds are decision-
    identical across all three backends sharing one compiled instance
    (the bound is compared exactly — the pallas interpret path performs
    the same f64 arithmetic, and the crossing code is shared)."""
    pytest.importorskip("jax")
    g, tg = _case(seed)
    r = rank_matrix(g, tg)
    q = priority_queue(hprv_b(g, tg, r), r.mean(1))
    inst = CompiledInstance(g, tg, rank=r)
    for alpha in (0.0, 0.85):
        s = inst.schedule(q, alpha=alpha, backend="scalar")
        v = inst.schedule(q, alpha=alpha, backend="vector")
        p = inst.schedule(q, alpha=alpha, backend="pallas")
        assert_identical(s, v)
        assert_decisions_identical(s, p)
        sb, bs = inst.schedule_with_bound(q, alpha, backend="scalar")
        pb, bp = inst.schedule_with_bound(q, alpha, backend="pallas")
        assert_decisions_identical(sb, pb)
        assert bs == pytest.approx(bp, rel=1e-9)


def test_three_way_wide_topology():
    """P = 8 single-route topology (where auto picks vector): the
    device lane batching must agree with both NumPy backends."""
    pytest.importorskip("jax")
    g, tg = _wide(8, 3)
    r = rank_matrix(g, tg)
    q = priority_queue(hprv_b(g, tg, r), r.mean(1))
    inst = CompiledInstance(g, tg, rank=r)
    for alpha in (0.0, 1.2):
        s = inst.schedule(q, alpha=alpha, backend="scalar")
        assert_identical(s, inst.schedule(q, alpha=alpha, backend="vector"))
        assert_decisions_identical(
            s, inst.schedule(q, alpha=alpha, backend="pallas"))


def test_update_replay_three_way():
    """update() replays decision-identically under pallas: same suffix
    start, same replay counters as scalar/vector."""
    pytest.importorskip("jax")
    rng = np.random.default_rng(2)
    tg = paper_topology()
    g = random_spg(40, rng, ccr=1.0, tg=tg, outdeg_constraint=True)
    policy = HVLB_CC_B(alpha_max=1.0, alpha_step=0.5)
    plans = {}
    for backend in ("scalar", "pallas"):
        sched = Scheduler(tg, policy=policy, backend=backend)
        plan = sched.submit(g)
        task = int(np.argmax(plan.schedule.start))
        plans[backend] = sched.update(task_rates={task: 1.5})
    ua, ub = plans["scalar"], plans["pallas"]
    assert_ran_pallas(ub)
    assert_decisions_identical(ua.schedule, ub.schedule)
    assert dataclasses.asdict(ua.replay) == dataclasses.asdict(ub.replay)


@pytest.mark.parametrize("record,resume", [("pallas", "scalar"),
                                           ("scalar", "pallas")])
def test_pallas_traces_portable(record, resume):
    """A trace recorded under pallas replays under scalar and vice
    versa (records hold plain floats; commits are shared scalar code)."""
    pytest.importorskip("jax")
    rng = np.random.default_rng(11)
    tg = paper_topology()
    g = random_spg(40, rng, ccr=1.0, tg=tg, outdeg_constraint=True)
    policy = HVLB_CC_B(alpha_max=1.0, alpha_step=0.5)
    sched = Scheduler(tg, policy=policy)
    plan = sched.submit(g, backend=record)
    task = int(np.argmax(plan.schedule.start))
    upd = sched.update(task_rates={task: 0.8}, backend=resume)
    assert plan.backend == record and plan.fallback is None
    assert upd.backend == resume and upd.fallback is None
    assert upd.replay.decisions_replayed > 0     # the resume actually ran
    fresh = Scheduler(tg, backend="scalar").submit(
        upd.graph, dataclasses.replace(policy, period=plan.period))
    assert_decisions_identical(upd.schedule, fresh.schedule)


def test_pallas_selection_end_to_end(monkeypatch):
    """backend="pallas" threads through every selection path — session
    default, per-call override, env var — and auto never picks it."""
    pytest.importorskip("jax")
    g, tg = paper_spg(), paper_topology()
    assert_ran_pallas(Scheduler(tg, backend="pallas").submit(g, ONE_POINT))
    assert_ran_pallas(Scheduler(tg).submit(g, ONE_POINT, backend="pallas"))
    monkeypatch.setenv("REPRO_SCHED_BACKEND", "pallas")
    assert_ran_pallas(Scheduler(tg).submit(g, ONE_POINT))
    monkeypatch.delenv("REPRO_SCHED_BACKEND")
    g8, tg8 = _wide(AUTO_VECTOR_MIN_P, 5)
    assert Scheduler(tg8).submit(g8, ONE_POINT).backend == "vector"


def test_paper_example_batched_waves(monkeypatch):
    """The paper queue decomposes into multi-task level waves: batch
    grouping (trace batch ids) is identical across backends, at least
    one wave has size > 1, and the per-wave pallas path pays exactly
    one kernel launch and one host round-trip per wave — O(levels), not
    O(decisions) — while the default scan path folds the whole plan
    into ONE launch / ONE round-trip (DESIGN.md §5)."""
    pytest.importorskip("jax")
    from collections import Counter

    g, tg = paper_spg(), paper_topology()
    r = rank_matrix(g, tg)
    q = priority_queue(hprv_b(g, tg, r), r.mean(1))
    inst = CompiledInstance(g, tg, rank=r)
    traces = {}
    for b in ("scalar", "vector", "pallas"):
        _, _, traces[b] = inst.schedule_traced(q, alpha=1.06, backend=b)
    bids = [rec[7] for rec in traces["scalar"].records]
    assert bids == [rec[7] for rec in traces["vector"].records]
    assert bids == [rec[7] for rec in traces["pallas"].records]
    counts = Counter(bids)
    assert max(counts.values()) > 1          # a wave of size > 1 ran
    n_waves = len(counts)
    assert n_waves < g.n                     # strictly fewer than decisions
    be = inst.backend_instance("pallas")
    l0, r0 = be.n_launches, be.n_roundtrips
    inst.schedule(q, alpha=1.06, backend="pallas")
    assert be.n_launches - l0 == 1           # whole plan, one dispatch
    assert be.n_roundtrips - r0 == 1
    monkeypatch.setenv("REPRO_PALLAS_SCAN", "0")
    l0, r0 = be.n_launches, be.n_roundtrips
    inst.schedule(q, alpha=1.06, backend="pallas")
    assert be.n_launches - l0 == n_waves     # per-wave fallback
    assert be.n_roundtrips - r0 == n_waves


def test_pallas_supports_link_reuse_routes():
    """Masked per-hop rows walk hops sequentially, so pallas accepts
    topologies whose routes revisit a link (vector refuses them)."""
    pytest.importorskip("jax")
    P = 3
    tg = _link_reuse_topology(P)
    g = random_spg(10, np.random.default_rng(0), ccr=1.0, tg=tg)
    r = rank_matrix(g, tg)
    q = priority_queue(hprv_b(g, tg, r), r.mean(1))
    inst = CompiledInstance(g, tg, rank=r)
    s = inst.schedule(q, alpha=0.5, backend="scalar")
    p = inst.schedule(q, alpha=0.5, backend="pallas")
    assert_decisions_identical(s, p)