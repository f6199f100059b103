"""The fused alpha sweep's deferred results (DESIGN.md §5).

One dispatch evaluates every alpha of the grid; the host reads each
alpha's makespan off the winners' EFTs at once and decodes an alpha's
decisions only when its schedule or trace is read.  Pinned here, bit for
bit, against the host loop (one eager single-alpha scan per alpha):

* every way of reading — ``makespans``, items, lazy traces, in any
  order, after the backend ran other plans in between — gives each
  alpha's makespan, schedule, bound and trace records;
* the session picks the same alpha* and sweep curve as the host loop;
* under faults the sweep raises for the same task;
* a fresh plan decodes one alpha, a fault replan all of them, each once
  (``backend.alphas_decoded``);
* threads racing to read one alpha all get the one object published;
* the fetched arrays are freed: what a sweep keeps is a copy of the
  winners' rows, under 16 MB at the benchmark cell's size.
"""
import gc
import os
import random
import sys
import threading
import weakref

import numpy as np
import pytest

from repro import tracing
from repro.core import (HVLB_CC_B, CompiledInstance, InfeasibleScheduleError,
                        Scheduler, paper_topology, random_spg)
from repro.core.engine import SweepSchedules
from repro.core.faults import FaultSpec
from repro.core.graph import SPG
from repro.core.ranks import hprv_b, priority_queue, rank_matrix
from repro.core.topology import fully_switched_topology

pytest.importorskip("jax")
import repro.core.backends.pallas as pb  # noqa: E402

RATE_PATTERNS = [(1.0, 0.67, 0.83), (0.83, 0.67, 1.0), (0.67, 0.83, 1.0)]
ALPHAS = [k * 0.25 for k in range(9)]
POLICY = HVLB_CC_B(alpha_max=2.0, alpha_step=0.25)
SEEDS = range(0, 200, 29)


def _case(seed: int):
    """The mixed-config generator of tests/test_engine_equivalence.py."""
    rng = np.random.default_rng(seed)
    tg = paper_topology(rates=RATE_PATTERNS[seed % 3])
    ccr = [0.1, 1.0, 10.0][(seed // 3) % 3]
    n = int(rng.integers(8, 31))
    g = random_spg(n, rng, ccr=ccr, tg=tg,
                   outdeg_constraint=(seed // 9) % 2 == 0)
    return g, tg


def _queue(g, tg):
    r = rank_matrix(g, tg)
    return r, priority_queue(hprv_b(g, tg, r), r.mean(1))


def assert_identical(a, b):
    assert np.array_equal(a.proc, b.proc)
    assert np.array_equal(a.start, b.start)
    assert np.array_equal(a.finish, b.finish)
    assert a.messages.keys() == b.messages.keys()
    for e, ma in a.messages.items():
        mb = b.messages[e]
        assert (ma.src_proc, ma.dst_proc, ma.route) == \
            (mb.src_proc, mb.dst_proc, mb.route)
        assert ma.intervals == mb.intervals


@pytest.fixture(params=["float64", "float32"])
def dtype(request, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_DTYPE", request.param)
    return request.param


@pytest.fixture(autouse=True)
def clean_recorder():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


@pytest.mark.parametrize("seed", SEEDS)
def test_fused_sweep_reads_like_the_host_loop(dtype, seed):
    g, tg = _case(seed)
    r, q = _queue(g, tg)
    inst = CompiledInstance(g, tg, rank=r)
    by_item = inst.schedule_sweep(q, ALPHAS, backend="pallas")
    by_trace = inst.schedule_sweep(q, ALPHAS, backend="pallas")
    # the host loop commits on the same backend after both dispatches:
    # a later decode must not read that run state
    ref = [inst.schedule_traced(q, a, want_bound=True, backend="pallas")
           for a in ALPHAS]
    assert isinstance(by_item, SweepSchedules) and len(by_item) == 9
    lazy = [by_trace.trace(a) for a in range(len(ALPHAS))]
    for tr, alpha in zip(lazy, ALPHAS):
        assert (tr.queue, tr.alpha, tr.period, tr.want_bound) == \
            (tuple(q), alpha, inst.default_period, True)
    for a in reversed(range(len(ALPHAS))):          # traces first
        assert lazy[a].records == ref[a][2].records
    for a, (s, bound, tr) in enumerate(by_item):    # items first
        s_ref, b_ref, tr_ref = ref[a]
        assert by_item.makespans[a] == s.makespan == s_ref.makespan
        assert by_trace.makespans[a] == s_ref.makespan
        assert_identical(s, s_ref)
        assert bound == b_ref
        assert tr.records == tr_ref.records
        assert by_item.trace(a).records == tr_ref.records
        s2, b2, _ = by_trace[a]
        assert_identical(s2, s_ref)
        assert b2 == b_ref
    assert by_item[-1][0] is by_item[len(ALPHAS) - 1][0]   # built once


@pytest.mark.parametrize("seed", SEEDS)
def test_session_selects_like_the_host_loop(dtype, seed, monkeypatch):
    g, tg = _case(seed)
    fused = Scheduler(tg, policy=POLICY, backend="pallas").submit(g)
    monkeypatch.setattr(pb.PallasBackend, "supports_plan_sweep",
                        lambda self: False)
    loop = Scheduler(tg, policy=POLICY, backend="pallas").submit(g)
    assert fused.backend == loop.backend == "pallas"
    assert fused.best_alpha == loop.best_alpha
    assert np.array_equal(fused.sweep.alphas, loop.sweep.alphas)
    assert np.array_equal(fused.sweep.makespans, loop.sweep.makespans)
    assert_identical(fused.schedule, loop.schedule)
    assert fused.replay.decisions_simulated == len(ALPHAS) * g.n


def _outcome(run):
    try:
        return run()
    except InfeasibleScheduleError as e:
        return ("infeasible", e.task, e.eft)


def _partition():
    """Two entries split across the processors and one join behind a
    dead link: no feasible placement for the join."""
    tg = fully_switched_topology(2, rates=[1.0, 1.0], link_speeds=[1.0, 1.0])
    g = SPG(n=3, edges=[(0, 2), (1, 2)], weights=[4.0, 4.0, 2.0],
            tpl={(0, 2): 2.0, (1, 2): 2.0})
    return g, tg


# a dead link: the partition; alpha 0 first fails at queue position 7,
# alpha 0.75 at 4 (seed 7); alphas 0 and 0.25 are feasible, the rest
# fail (seed 23); every alpha fails (seed 3); none fails (seed 116)
@pytest.mark.parametrize("seed", [None, 7, 23, 3, 116])
def test_faulted_sweep_raises_like_the_host_loop(dtype, seed):
    g, tg = _partition() if seed is None else _case(seed)
    link = "l1"
    r, q = _queue(g, tg)
    inst = CompiledInstance(g, tg, rank=r, faults=FaultSpec(
        link_factors=((link, float("inf")),)))

    def loop():
        return [inst.schedule_traced(q, a, want_bound=True,
                                     backend="pallas")[0].makespan
                for a in ALPHAS]

    def fused():
        return inst.schedule_sweep(q, ALPHAS,
                                   backend="pallas").makespans.tolist()

    want = _outcome(loop)
    assert _outcome(fused) == want
    if seed is None:
        assert want[:2] == ("infeasible", 2)
    else:
        assert (want[0] == "infeasible") == (seed != 116)


def test_alphas_decoded_counts_the_alphas_read(monkeypatch):
    tg = paper_topology()
    g = random_spg(20, np.random.default_rng(5), ccr=1.0, tg=tg,
                   outdeg_constraint=True)
    pol = HVLB_CC_B(alpha_max=5.0, alpha_step=0.05)
    built = []
    build = SweepSchedules._build

    def spy(self, a):
        built.append((self, a))
        return build(self, a)

    monkeypatch.setattr(SweepSchedules, "_build", spy)
    tracing.enable()

    def decoded(call):
        tracing.reset()
        del built[:]
        plan = call()
        assert tracing.snapshot()["counters"].get(
            "backend.alphas_decoded", 0) == len(built)
        return plan, list(built)

    s = Scheduler(tg, policy=pol, backend="pallas")
    plan, fresh = decoded(lambda: s.submit(g))
    assert len(plan.sweep.alphas) == 101
    sweep = fresh[0][0]
    k_best = int(np.flatnonzero(plan.sweep.alphas == plan.best_alpha)[0])
    assert fresh == [(sweep, k_best)]                   # alpha* alone
    upd, resumed = decoded(lambda: s.update(task_rates={
        int(np.argmax(plan.schedule.start)): 1.4}))
    assert upd.replay.sims_resumed > 0 and upd.replay.sims_full == 0
    # the alphas the update's host loop resumed (it keeps a trace for
    # each), less alpha*, which the submit built
    grid = plan.sweep.alphas.tolist()
    assert all(sw is sweep for sw, _ in resumed)
    assert {grid[a] for _, a in resumed} == \
        set(s._last.traces[pol]) - {plan.best_alpha}
    assert len(s._last.traces[pol]) == upd.replay.sims_resumed

    s = Scheduler(tg, policy=pol, backend="pallas")
    plan, fresh = decoded(lambda: s.submit(g))
    _, faulted = decoded(lambda: s.mark_failed(proc=1))
    first = fresh[0][0]
    every = sorted(a for sw, a in fresh + faulted if sw is first)
    assert every == list(range(101))                   # each alpha once


def test_racing_readers_get_one_published_build():
    g, tg = _case(29)
    r, q = _queue(g, tg)
    inst = CompiledInstance(g, tg, rank=r)
    swept = inst.schedule_sweep(q, ALPHAS, backend="pallas")
    traces = [swept.trace(a) for a in range(len(ALPHAS))]
    workers = (os.cpu_count() or 4) + 2
    seen = [[] for _ in ALPHAS]
    start = threading.Barrier(workers)

    def read(seed):
        order = list(range(len(ALPHAS))) * 3
        random.Random(seed).shuffle(order)
        start.wait(timeout=30)
        for a in order:
            got = (id(traces[a].records), id(swept[a][0]),
                   id(swept.trace(a).records))
            seen[a].append(got)

    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=read, args=(k,))
                   for k in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    for a in range(len(ALPHAS)):
        assert len(seen[a]) == 3 * workers
        (rec, sched, rec2), = set(seen[a])          # one build, shared
        assert rec == rec2


def test_sweep_keeps_copies_of_the_winners_rows(monkeypatch):
    """At the benchmark cell's size (n=300, P=16, 101 alphas) nothing
    holds the fetched arrays after the sweep; what it keeps totals
    under 16 MB."""
    P = 16
    tg = fully_switched_topology(
        P, rates=np.tile([1.0, 0.67, 0.83], 6)[:P],
        link_speeds=np.tile([1.0, 1.0, 3.0, 1.0], 4))
    g = random_spg(300, np.random.default_rng(3), ccr=1.0, tg=tg,
                   max_in=2, max_out=3)
    r, q = _queue(g, tg)
    inst = CompiledInstance(g, tg, rank=r)
    fetched, kept = [], []
    dispatch, gather = pb.PallasBackend._scan_dispatch, pb._gather_winners

    def watch_fetch(self, waves, alphas):
        out = dispatch(self, waves, alphas)
        fetched.extend(weakref.ref(x) for x in out)
        return out

    def watch_gather(*args):
        out = gather(*args)
        kept.extend(out)
        return out

    monkeypatch.setattr(pb.PallasBackend, "_scan_dispatch", watch_fetch)
    monkeypatch.setattr(pb, "_gather_winners", watch_gather)
    alphas = [k * 0.05 for k in range(101)]
    swept = inst.schedule_sweep(q, alphas, backend="pallas")
    gc.collect()
    assert len(fetched) == len(kept) == 8
    assert all(ref() is None for ref in fetched)       # the fetch is gone
    assert sum(x.nbytes for x in kept) < 16e6
    assert kept[0].shape == (101, 300)
    s, _, _ = swept[int(np.argmin(swept.makespans))]
    assert s.makespan == swept.makespans.min()
