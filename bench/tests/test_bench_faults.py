"""Whole runs of the submit cell at a small size on the CPU, past the
look for a chip: sound as the program is, and ``correct`` false with the
timed path broken underneath (an answer altered where the device
produces it, half of every wave left out, a plan that returns its state
unchanged, the alphas that were not chosen off) or with the bfloat16
control in the program's place.  The cell runs on one chip, so no
exchange between chips exists to leave out."""
import json
import os

import jax  # noqa: F401  (imported before a run sets the cache path)
import numpy as np
import pytest

import run as harness
from benchlib import reference as R

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def small_cell():
    spec = _load(ROOT, "BENCHMARK.json")
    cell = spec["workloads"][0]
    cfg = _load(BENCH, "configs", cell["config"] + ".json")
    cfg["cluster"].update(procs=4, rates=cfg["cluster"]["rates"][:4],
                          speeds=cfg["cluster"]["speeds"][:4])
    cfg["graphs"]["n"] = 24
    cfg["policy"].update(alpha_max=0.5, alpha_step=0.25)
    tr = dict(_load(BENCH, "traffic", cell["traffic"] + ".json"), pool=2)
    return {"spec": spec, "cell": cell, "config": cfg, "traffic": tr}


@pytest.fixture
def run_cell(monkeypatch):
    """One run of a small cell; restores what a run sets process-wide."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    before = jax.config.jax_persistent_cache_min_compile_time_secs

    def go(seconds=1):
        found = small_cell()
        return harness.run(["--workload", found["cell"]["name"],
                            "--seed", str(2**31 + 99), "--seconds",
                            str(seconds), "--trace", "0"],
                           require_tpu=False, found=found)

    yield go
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


@pytest.fixture
def pallas():
    return pytest.importorskip("repro.core.backends.pallas")


def _alter_fetch(monkeypatch, pb, how):
    """Break the device's answers as the scan hands them back."""
    dispatch = pb.PallasBackend._scan_dispatch

    def broken(self, waves, alphas):
        win, est, eft, *rest = [np.array(x) for x in
                                dispatch(self, waves, alphas)]
        P = self.inst.P
        if how == "altered":                 # one answer, where produced
            win[..., 0, 0] = (win[..., 0, 0] + 1) % P
        else:                                # half of every wave left out
            half = win.shape[-1] // 2
            win[..., half:] = win[..., :1]
            est[..., half:, :] = est[..., :1, :]
            eft[..., half:, :] = eft[..., :1, :]
        return (win, est, eft, *rest)

    monkeypatch.setattr(pb.PallasBackend, "_scan_dispatch", broken)


def test_sound_run_is_correct(run_cell):
    res = run_cell()
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("how", ["altered", "half"])
def test_broken_scan_output_is_not_correct(run_cell, pallas, monkeypatch,
                                           how):
    _alter_fetch(monkeypatch, pallas, how)
    res = run_cell()
    assert not res["correct"], res["checks"]


def test_submit_returning_stale_plan_is_not_correct(run_cell, monkeypatch):
    import repro.core as core

    submit, first = core.Scheduler.submit, []

    def stale(self, g, *a, **k):
        plan = submit(self, g, *a, **k)
        first.append(first[0] if first else plan)
        return first[-1]

    monkeypatch.setattr(core.Scheduler, "submit", stale)
    assert not run_cell()["correct"]


def test_sweep_with_other_alphas_off_is_not_correct(run_cell, monkeypatch):
    """The alphas that were not chosen, off by 1 %, as a sweep computed
    in a lower precision would be: the chosen plan alone still holds."""
    import repro.core as core

    submit = core.Scheduler.submit

    def skewed(self, g, *a, **k):
        plan = submit(self, g, *a, **k)
        ms = np.array(plan.sweep.makespans, dtype=float)
        best = list(plan.sweep.alphas).index(plan.best_alpha)
        ms[np.arange(len(ms)) != best] *= 1.01
        plan.sweep.makespans[:] = ms
        return plan

    monkeypatch.setattr(core.Scheduler, "submit", skewed)
    res = run_cell()
    assert not res["correct"]
    assert res["checks"]["faults"]["value"] == 0
    assert res["checks"]["sweep_off_share"]["value"] > \
        res["checks"]["sweep_off_share"]["limit"]


def test_bf16_control_in_the_programs_place_is_not_correct(run_cell,
                                                           monkeypatch):
    import repro.core as core
    from repro.core.api import ReplayStats, SweepResult

    cfg = small_cell()["config"]
    from loads import program

    c = R.switched_cluster(*program.cluster_params(cfg["cluster"]))
    grid = program.alpha_grid(cfg["policy"])

    class Plan:
        backend, fallback = "pallas", None

    def control(self, g, *a, **k):
        pp = R.prepare(R.Graph(g.n, list(g.edges), g.weights, dict(g.tpl)),
                       c)
        best, walks = R.best_of(pp, grid, rnd=R.bf16)
        w = walks[grid.index(best)]
        plan = Plan()
        plan.schedule = core.Schedule(g, self.topology, w.proc, w.start,
                                      w.finish, {}, alpha=best)
        plan.sweep = SweepResult(plan.schedule, best, np.array(grid),
                                 np.array([x.makespan for x in walks]))
        plan.best_alpha, plan.makespan = best, w.makespan
        plan.replay = ReplayStats(0, 0, 0, 0, len(grid))
        return plan

    monkeypatch.setattr(core.Scheduler, "submit", control)
    res = run_cell()
    assert not res["correct"]
    assert res["checks"]["faults"]["value"] == 0      # only the numbers
