"""The expert-parallel step cell: its draws, its plain graph and mesh,
and whole runs at a small size on the CPU under the chip's numerics
(float32, tile-padded), past the look for a chip: correct as the
program is, and ``correct`` false with a program answer altered, with a
graph volume altered, or with a plan computed in bfloat16."""
import json
import os
import types

import jax  # noqa: F401  (imported before a run sets the cache path)
import numpy as np
import pytest

import run as harness
from benchlib import ep_graph as EG
from benchlib import reference as R

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELL = "dbrx-ep16-mesh4x4-prefill.submit"


def _load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def small_cell():
    """The cell on a 2x2 mesh: 4 ranks, 2 layers, 32-token chunks, a
    3-alpha grid, a pool of 2; dbrx's widths and 16 experts."""
    spec = _load(ROOT, "BENCHMARK.json")
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    cfg = _load(BENCH, "configs", cell["config"] + ".json")
    cfg["model"]["n_layers"] = 2
    cfg["step"].update(ranks=4, chunk=32, context=128)
    cfg["cluster"].update(nx=2, ny=2, degraded={"1": 0.8})
    cfg["policy"].update(alpha_max=0.1)
    cfg["check"]["sampled_alphas"] = 1
    tr = dict(_load(BENCH, "traffic", cell["traffic"] + ".json"), pool=2)
    return {"spec": spec, "cell": cell, "config": cfg, "traffic": tr}


# ------------------------------------------------------ draws and graph
def test_draws_are_seeded_and_route_top_k():
    cfg = small_cell()["config"]
    a = EG.draw_step(np.random.default_rng(5), cfg["model"], cfg["step"])
    b = EG.draw_step(np.random.default_rng(5), cfg["model"], cfg["step"])
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    tokens, kv = a
    assert tokens.shape == (2, 2, 4, 16)
    assert (tokens.sum(axis=3) == 32 * 4).all()      # chunk x top-4
    assert (tokens <= 32).all()                      # distinct experts
    assert kv.shape == (2, 4) and set(kv.ravel()) <= {32, 64, 96, 128}


def test_step_graph_by_hand():
    model = {"n_experts": 2, "top_k": 1, "d_model": 4, "d_ff": 8,
             "n_heads": 2, "n_kv_heads": 1, "head_dim": 2}
    tokens = np.array([[[[3, 1]]], [[[2, 2]]]])       # 2 layers, 1 rank
    c = EG.mesh_cluster({"nx": 2, "ny": 1, "peak_flops": 20.0, "mfu": 0.5,
                         "link_bw": 4.0})
    g, labels = EG.step_graph(model, tokens, np.array([[8]]), c)
    assert g.n == 6 and labels[0] == (0, 0, "attn", 0)
    assert labels[4] == (1, 0, "expert", 0)
    # attention of 4 tokens against 8 keys: 4*T*D*H*dh + 4*T*D*KV*dh +
    # 4*T*kv*H*dh + router 2*T*D*E
    assert g.weights[0] == 4 * 4 * 4 * 2 * 2 + 4 * 4 * 4 * 1 * 2 \
        + 4 * 4 * 8 * 2 * 2 + 2 * 4 * 4 * 2
    assert g.weights[1] == 3 * 2 * 3 * 4 * 8          # expert 0: 3 tokens
    assert g.tpl[(0, 1)] == 3 * 4 * 2 and g.tpl[(2, 3)] == 1 * 4 * 2
    assert g.tpl[(1, 3)] == 3 * 4 * 2                 # combine, layer 0
    assert sorted(g.edges) == [(0, 1), (0, 2), (1, 3), (2, 3), (3, 4),
                               (3, 5)]
    # computation times at 10 FLOP/s; off home also the bytes streamed
    # over the 4 byte/s link: rank 0's KV cache of 8 keys (8 * 2 * 1 * 2
    # bf16 values), expert 1's weights (3 * 4 * 8 int8 values)
    assert g.comp(0, 0, c) == g.weights[0] / 10.0
    assert g.comp(0, 1, c) == g.weights[0] / 10.0 + 8 * 2 * 1 * 2 * 2 / 4.0
    assert g.comp(2, 1, c) == g.weights[2] / 10.0
    assert g.comp(2, 0, c) == g.weights[2] / 10.0 + 3 * 4 * 8 / 4.0


def test_mesh_cluster_routes():
    c = EG.mesh_cluster({"nx": 3, "ny": 2, "peak_flops": 10.0, "mfu": 0.5,
                         "link_bw": 2.0, "degraded": {"4": 0.5}})
    assert c.rates == [5.0, 5.0, 5.0, 5.0, 2.5, 5.0]
    assert len(c.link_speed) == 7
    assert c.routes[(0, 5)] == [("x0.0", "x1.0", "y2.0"),
                                ("y0.0", "x0.1", "x1.1")]
    assert c.routes[(5, 3)] == [("x1.1", "x0.1")]
    assert c.proc_speed == [2.0] * 6


def test_float32_checker_holds_a_float32_walk_and_faults_bfloat16():
    rng = np.random.default_rng(2)
    cfg = small_cell()["config"]
    c = EG.mesh_cluster(cfg["cluster"])
    g, _ = EG.step_graph(cfg["model"],
                         *EG.draw_step(rng, cfg["model"], cfg["step"]), c)
    pp = R.prepare(g, c)
    limits = cfg["limits"]
    for rnd, ok in ((EG.f32, True), (R.bf16, False)):
        w = R.walk(pp, rnd(0.05), rnd=rnd)
        chk = EG.RoundedChecker(limits, EG.f32)
        chk.schedule(pp, 0.05, w.proc, w.start, w.finish, w.makespan, "p")
        assert chk.correct == ok, chk.worst
    assert chk.worst["time_err"] > limits["time_err"]


# ------------------------------------------------------------- whole runs
@pytest.fixture
def run_cell(monkeypatch):
    """One run of the small cell under the chip's numerics; restores
    what a run sets process-wide."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    monkeypatch.setenv("REPRO_PALLAS_DTYPE", "float32")
    monkeypatch.setenv("REPRO_PALLAS_TILE", "1")
    before = jax.config.jax_persistent_cache_min_compile_time_secs

    def go(trace=0):
        found = small_cell()
        return harness.run(["--workload", CELL, "--seed",
                            str(2**31 + 15), "--seconds", "1",
                            "--trace", str(trace)],
                           require_tpu=False, found=found)

    yield go
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before)


def test_sound_run_is_correct_and_traced_run_reports(run_cell):
    res = run_cell(trace=1)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    m = res["metrics"]
    for name in ("backend.upload_mb_per_plan.ep16",
                 "backend.fetch_mb_per_plan.ep16",
                 "backend.hop_steps_per_plan.ep16",
                 "backend.tables_ms_per_plan.ep16",
                 "engine.decode_ms_per_plan.ep16",
                 "api.sims_per_plan.ep16",
                 "backend.dispatch_ms_per_plan.ep16",
                 "backend.dispatches_per_plan.ep16",
                 "device.idle_pct.ep16"):
        assert m[name]["value"] >= 0, name
    # 4 alphas (3 padded), 4 ranks + 16 experts a block: in-degree 16,
    # two routes of up to 2 hops on the 2x2 mesh
    steps = m["backend.hop_steps_per_plan.ep16"]["value"]
    assert steps % (4 * 16 * 2 * 2) == 0
    from repro import tracing
    assert not tracing.enabled()
    assert list(res)[-1] == "checks"


def test_altered_answer_is_not_correct(run_cell, monkeypatch):
    pb = pytest.importorskip("repro.core.backends.pallas")
    dispatch = pb.PallasBackend._scan_dispatch

    def altered(self, waves, alphas):
        win, *rest, bestr = [np.array(x)
                             for x in dispatch(self, waves, alphas)]
        # the first decision, an attention task of layer 0, moved; every
        # message on its pair's first route, which every pair has, so
        # the decode reads the answer
        win[..., 0, 0] = (win[..., 0, 0] + 1) % self.inst.P
        return (win, *rest, np.zeros_like(bestr))

    monkeypatch.setattr(pb.PallasBackend, "_scan_dispatch", altered)
    res = run_cell()
    assert not res["correct"], res["checks"]
    assert res["checks"]["decision_gap"]["value"] > \
        res["checks"]["decision_gap"]["limit"]


def test_altered_graph_volume_is_not_correct(run_cell, monkeypatch):
    from repro import planner

    build = planner.ep_step_graph

    def altered(*a, **k):
        g = build(*a, **k)
        e = g.edges[len(g.edges) // 2]
        g.tpl[e] *= 1 + 1e-9
        return g

    monkeypatch.setattr(planner, "ep_step_graph", altered)
    res = run_cell()
    assert not res["correct"]
    assert res["checks"]["faults"]["value"] > 0


def test_altered_computation_time_is_not_correct(run_cell, monkeypatch):
    from repro import planner

    build = planner.ep_step_graph

    def altered(*a, **k):
        g = build(*a, **k)
        g.comp_matrix[g.n // 2, 0] *= 1 + 1e-9
        return g

    monkeypatch.setattr(planner, "ep_step_graph", altered)
    res = run_cell()
    assert not res["correct"]
    assert res["checks"]["faults"]["value"] > 0


def test_bf16_plan_in_the_programs_place_is_not_correct(run_cell,
                                                        monkeypatch):
    """The reference's own sweep in bfloat16, handed back as the plan:
    one precision below the stated float32."""
    from repro import planner

    cfg = small_cell()["config"]
    c = EG.mesh_cluster(cfg["cluster"])
    grid = [0.0, 0.05, 0.1]

    def control(g, tg, *a, **k):
        pp = R.prepare(EG.ResidentGraph(g.n, list(g.edges), g.weights,
                                        dict(g.tpl), g.comp_matrix), c)
        best, walks = R.best_of(pp, grid, rnd=R.bf16)
        w = walks[grid.index(best)]
        sweep = types.SimpleNamespace(
            alphas=np.array(grid), makespans=np.array([x.makespan
                                                       for x in walks]))
        plan = types.SimpleNamespace(
            backend="pallas", fallback=None, sweep=sweep, best_alpha=best,
            replay=types.SimpleNamespace(sims_full=len(grid),
                                         sims_resumed=0),
            makespan=w.makespan,
            schedule=types.SimpleNamespace(proc=w.proc, start=w.start,
                                           finish=w.finish))
        return types.SimpleNamespace(plan=plan, makespan_s=w.makespan)

    monkeypatch.setattr(planner, "plan_placement", control)
    res = run_cell()
    assert not res["correct"]
    assert res["checks"]["faults"]["value"] == 0      # only the numbers


def test_program_without_the_planner_fails_at_once(run_cell, monkeypatch):
    from repro import planner

    monkeypatch.delattr(planner, "tpu_mesh_topology")
    with pytest.raises(ImportError):
        run_cell()
