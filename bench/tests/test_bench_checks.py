"""The comparison that decides ``correct``: the reference walk with the
served decisions forced, its numbers and their limits."""
import json
import os

import numpy as np

from benchlib import gen
from benchlib import reference as R
from benchlib.checks import Checker

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(BENCH, "configs", "tgff-p16-n300.json")) as f:
    LIMITS = json.load(f)["limits"]


def case(seed=3, n=40, P=4):
    rng = np.random.default_rng(seed)
    c = R.switched_cluster(rng.uniform(0.6, 1.2, P).tolist(),
                           rng.uniform(0.5, 3.0, P).tolist())
    g = gen.random_graph(n, rng, c, max_in=3, max_out=6, ccr=1.0)
    return R.prepare(g, c)


def checked(pp, alpha, proc, start, finish, makespan):
    chk = Checker(LIMITS)
    chk.schedule(pp, alpha, proc, start, finish, makespan, "plan")
    return chk


def test_reference_schedule_passes():
    pp = case()
    w = R.walk(pp, 0.5)
    chk = checked(pp, 0.5, w.proc, w.start, w.finish, w.makespan)
    assert chk.correct
    assert chk.worst == {"decision_gap": 0.0, "time_err": 0.0,
                         "makespan_err": 0.0, "sweep_off_share": 0.0,
                         "best_gap": 0.0}


def test_changed_processor_is_rejected():
    pp = case()
    w = R.walk(pp, 0.5)
    proc = w.proc.copy()
    j = pp.queue[len(pp.queue) // 2]
    proc[j] = (proc[j] + 1) % pp.c.n_procs
    chk = checked(pp, 0.5, proc, w.start, w.finish, w.makespan)
    assert not chk.correct
    assert chk.worst["decision_gap"] > LIMITS["decision_gap"]


def test_shifted_finish_time_is_rejected():
    pp = case()
    w = R.walk(pp, 0.5)
    finish = w.finish.copy()
    finish[pp.queue[5]] += 1e-2 * w.makespan
    chk = checked(pp, 0.5, w.proc, w.start, finish, w.makespan)
    assert not chk.correct
    assert chk.worst["time_err"] > LIMITS["time_err"]


def test_wrong_makespan_is_rejected():
    pp = case()
    w = R.walk(pp, 0.5)
    chk = checked(pp, 0.5, w.proc, w.start, w.finish, w.makespan * 1.01)
    assert chk.worst["makespan_err"] > LIMITS["makespan_err"]
    assert not chk.correct


def test_near_tie_flip_is_accepted():
    """A choice within the near-tie band of the best is the policy's
    own latitude: with two identical processors every decision between
    them is a dead heat, and the mirror schedule (the two swapped
    throughout) must pass with a gap of 0."""
    rng = np.random.default_rng(11)
    rates = rng.uniform(0.6, 1.2, 6).tolist()
    speeds = rng.uniform(0.5, 3.0, 6).tolist()
    rates[4], speeds[4] = rates[1], speeds[1]
    c = R.switched_cluster(rates, speeds)
    pp = R.prepare(gen.random_graph(60, rng, c, max_in=3, max_out=6,
                                    ccr=1.0), c)
    w = R.walk(pp, 0.5)
    assert {1, 4} <= set(w.proc.tolist())
    mirror = np.where(w.proc == 1, 4, np.where(w.proc == 4, 1, w.proc))
    chk = checked(pp, 0.5, mirror, w.start, w.finish, w.makespan)
    assert chk.correct
    assert chk.worst["decision_gap"] == 0.0


def test_missing_plan_or_fault_is_not_correct():
    assert not Checker(LIMITS).correct          # nothing checked
    pp = case()
    w = R.walk(pp, 0.5)
    chk = checked(pp, 0.5, w.proc, w.start, w.finish, w.makespan)
    chk.fault("an answer never came")
    assert not chk.correct


def test_bf16_control_fails():
    """The control: the reference in bfloat16 in the program's place."""
    pp = case(seed=5, n=60, P=4)
    w = R.walk(pp, 0.5, rnd=R.bf16)
    chk = checked(pp, 0.5, w.proc, w.start, w.finish, w.makespan)
    assert not chk.correct
    assert chk.worst["time_err"] > LIMITS["time_err"]


def sweep_checked(pp, grid, sweep, makespan):
    ref = [w[0] for w in R.walk_many([pp], [(0, a) for a in grid])]
    chk = Checker(LIMITS)
    chk.sweep(ref, sweep, makespan, "plan")
    return chk, ref


def test_reference_sweep_passes():
    pp = case()
    grid = [0.0, 0.5, 1.0, 1.5]
    sweep = [R.walk(pp, a).makespan for a in grid]
    chk, _ = sweep_checked(pp, grid, sweep, min(sweep))
    assert chk.worst["sweep_off_share"] == 0.0
    assert chk.worst["best_gap"] == 0.0


def test_sweep_with_alphas_off_is_rejected():
    """Alphas that were not chosen still hold the plan to account: a
    sweep whose other makespans are off fails, though its best holds."""
    pp = case()
    grid = [0.0, 0.5, 1.0, 1.5]
    sweep = [R.walk(pp, a).makespan for a in grid]
    k = sweep.index(min(sweep))
    off = [m if a == k else m * 1.01 for a, m in enumerate(sweep)]
    chk, _ = sweep_checked(pp, grid, off, sweep[k])
    assert chk.worst["sweep_off_share"] == 0.75
    assert chk.worst["sweep_off_share"] > LIMITS["sweep_off_share"]
    assert chk.worst["best_gap"] == 0.0
    missing = [m if a == k else float("inf") for a, m in enumerate(sweep)]
    assert sweep_checked(pp, grid, missing, sweep[k])[0].worst[
        "sweep_off_share"] == 0.75


def test_makespan_above_the_references_best_is_rejected():
    pp = case()
    grid = [0.0, 0.5, 1.0, 1.5]
    sweep = [R.walk(pp, a).makespan for a in grid]
    chk, ref = sweep_checked(pp, grid, sweep, min(sweep) * 1.1)
    assert chk.worst["best_gap"] > LIMITS["best_gap"]
    assert not chk.correct


def test_walk_many_matches_walk_in_worker_processes():
    pp = case(n=20)
    jobs = [(0, 0.1 * k) for k in range(16)]
    got = R.walk_many([pp], jobs, workers=2)
    assert [g[0] for g in got] == [R.walk(pp, a).makespan
                                   for _, a in jobs]
    assert (got[3][1] == R.walk(pp, 0.3).proc).all()


def test_bf16_rounding():
    assert R.bf16(1.0) == 1.0
    assert R.bf16(1.0 + 2 ** -9) == 1.0             # ties to even
    assert R.bf16(1.0 + 3 * 2 ** -9) == 1.0 + 2 ** -7
    assert abs(R.bf16(3.14159) - 3.14159) < 3.14159 * 2 ** -8
