"""Expert-parallel placement: the MoE prefill step graph
(``planner.ep_step_graph``), the 2D ICI mesh (``planner.tpu_mesh_topology``),
and their plans through ``plan_placement`` on every backend."""
import numpy as np
import pytest

from repro import tracing
from repro.configs.base import ModelConfig
from repro.planner import (HW, ep_step_graph, plan_placement,
                           tpu_mesh_topology)

# a small MoE: 4 experts, top-2, GQA 4 heads over 2 KV heads of width 16
TINY = ModelConfig(name="tiny-moe", family="moe", n_layers=4, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=96, vocab=100,
                   n_experts=4, top_k=2)


def routing(rng, L, M, D, E, chunk, k):
    """tokens[l, m, d, e]: each of a chunk's tokens to k distinct
    experts."""
    tok = np.zeros((L, M, D, E), dtype=np.int64)
    for idx in np.ndindex(L, M, D):
        picks = [rng.choice(E, k, replace=False) for _ in range(chunk)]
        tok[idx] = np.bincount(np.ravel(picks), minlength=E)
    return tok


def step(cfg=TINY, L=2, M=2, D=4, chunk=8, seed=0):
    rng = np.random.default_rng(seed)
    tok = routing(rng, L, M, D, cfg.n_experts, chunk, cfg.top_k)
    kv = chunk * (1 + rng.integers(0, 4, size=(M, D)))
    return tok, kv


# ------------------------------------------------------------ the graph
def test_ep_graph_shape_and_fan():
    tok, kv = step(L=3, M=2, D=4)
    g = ep_step_graph(TINY, tok, kv, 2, tpu_mesh_topology(2, 2))
    assert g.n == 3 * 2 * (4 + 4)
    # dispatch 16 a (layer, microbatch); combine into the next layer
    assert len(g.edges) == 3 * 2 * 16 + 2 * 2 * 16
    kinds = {t: g.ep_tasks[t][2] for t in range(g.n)}
    for t, (l, m, kind, i) in g.ep_tasks.items():
        assert g.ep_tasks[t] == (l, m, kind, i)
        if kind == "attn":
            assert len(g.succ[t]) == 4
            assert all(kinds[s] == "expert" for s in g.succ[t])
            assert len(g.pred[t]) == (0 if l == 0 else 4)
        else:
            assert len(g.pred[t]) == 4
            assert len(g.succ[t]) == (4 if l < 2 else 0)
            assert all(g.ep_tasks[s][:2] == (l + 1, m) for s in g.succ[t])
    assert sorted(i for (_, _, k, i) in g.ep_tasks.values()
                  if k == "expert") == sorted(list(range(4)) * 6)


def test_ep_graph_flops_and_bytes_by_hand():
    """Two layers, one microbatch, two ranks of a 2-token chunk, on a
    1x2 mesh of chips at 100 FLOP/s joined by one 4 byte/s link."""
    cfg = ModelConfig(name="hand", family="moe", n_layers=2, d_model=8,
                      n_heads=2, n_kv_heads=1, d_ff=16, vocab=10,
                      n_experts=4, top_k=2)
    tok = np.zeros((2, 1, 2, 4), dtype=np.int64)
    tok[:, 0, 0] = [2, 1, 1, 0]          # rank 0: 2 tokens x 2 experts
    tok[:, 0, 1] = [0, 2, 0, 2]
    kv = np.array([[6, 2]])
    tg = tpu_mesh_topology(2, 1, HW(peak_flops=200.0, mfu=0.5, ici_bw=4.0))
    g = ep_step_graph(cfg, tok, kv, 1, tg)
    # attention: q, o projections 2*(2*T*D*H*dh) = 2*(2*2*8*2*4) = 512,
    # k, v 2*(2*2*8*1*4) = 256, scores and values 2*(2*T*kv*H*dh):
    # rank 0 (kv 6) 2*(2*2*6*2*4) = 384, rank 1 (kv 2) 128; router
    # 2*T*D*E = 2*2*8*4 = 128
    assert g.weights[0] == 512 + 256 + 384 + 128
    assert g.weights[1] == 512 + 256 + 128 + 128
    # expert e: SwiGLU 3 * 2*n*D*F over its n routed tokens
    routed = [2, 3, 1, 2]
    for e in range(4):
        assert g.weights[2 + e] == 3 * 2 * routed[e] * 8 * 16
    # message of n tokens: n * D bf16 values
    assert g.tpl[(0, 2)] == 2 * 8 * 2
    assert g.tpl[(1, 3)] == 2 * 8 * 2
    assert g.tpl[(1, 2)] == 0.0              # present, no tokens
    assert g.tpl[(2, 6)] == 2 * 8 * 2        # combine into layer 1
    assert g.tpl[(5, 7)] == 2 * 8 * 2
    # at home (rank d and expert e on chip d, e mod 2) FLOPs / rate; off
    # home also the bytes it streams from home over the 4 byte/s link:
    # rank 0's KV cache 6 keys x 2 x 1 KV head x width 4 x 2 bytes, an
    # expert's int8 weights 3 x D x F
    assert list(g.ep_home[:6]) == [0, 1, 0, 1, 0, 1]
    cm = g.comp_matrix
    assert cm[0, 0] == g.weights[0] / 100.0
    assert cm[0, 1] == g.weights[0] / 100.0 + 6 * 2 * 1 * 4 * 2 / 4.0
    assert cm[1, 0] == g.weights[1] / 100.0 + 2 * 2 * 1 * 4 * 2 / 4.0
    assert cm[3, 1] == g.weights[3] / 100.0
    assert cm[3, 0] == g.weights[3] / 100.0 + 3 * 8 * 16 / 4.0


@pytest.mark.parametrize("bad", ["rank", "sum", "kv", "mb", "layers"])
def test_ep_graph_rejects_bad_routing(bad):
    tok, kv = step(L=2, M=2, D=4)
    M = 2
    if bad == "rank":
        tok = tok[0]
    elif bad == "sum":
        tok = tok.copy()
        tok[1, 0, 0, 0] += 1
    elif bad == "kv":
        kv = kv[:, :3]
    elif bad == "mb":
        M = 3
    else:
        tok = np.concatenate([tok] * 3)       # 6 layers of a 4-layer model
    with pytest.raises(ValueError):
        ep_step_graph(TINY, tok, kv, M, tpu_mesh_topology(2, 2))


def test_ep_graph_build_is_a_span():
    tok, kv = step()
    tracing.reset()
    tracing.enable()
    try:
        ep_step_graph(TINY, tok, kv, 2, tpu_mesh_topology(2, 2))
        snap = tracing.snapshot()
    finally:
        tracing.disable()
    assert snap["spans"]["repro.planner.ep_graph"]["count"] == 1


# ------------------------------------------------------------- the mesh
def test_mesh_routes_xy_then_yx():
    tg = tpu_mesh_topology(4, 4)
    # chip (0, 0) to chip (3, 3): X first along row 0, Y first along
    # column 0; 6 hops each
    assert tg.routes[(0, 15)] == [
        ("x0.0", "x1.0", "x2.0", "y3.0", "y3.1", "y3.2"),
        ("y0.0", "y0.1", "y0.2", "x0.3", "x1.3", "x2.3")]
    # chips that share a row or a column: one straight route
    assert tg.routes[(0, 3)] == [("x0.0", "x1.0", "x2.0")]
    assert tg.routes[(13, 1)] == [("y1.2", "y1.1", "y1.0")]
    assert len(tg.link_speed) == 24                 # 2 * 4 * 3 edges
    assert set(tg.link_speed.values()) == {HW().ici_bw}


@pytest.mark.parametrize("nx,ny", [(4, 4), (3, 2), (1, 3)])
def test_mesh_hops_and_symmetry(nx, ny):
    tg = tpu_mesh_topology(nx, ny)
    P = nx * ny
    assert tg.n_procs == P
    for a in range(P):
        for b in range(P):
            if a == b:
                continue
            dx, dy = abs(a % nx - b % nx), abs(a // nx - b // nx)
            rr = tg.routes[(a, b)]
            assert len(rr) == (2 if dx and dy else 1)
            assert all(len(r) == dx + dy for r in rr)
            assert all(len(set(r)) == len(r) for r in rr)
            # the way back is the same links: X-first one way is
            # Y-first the other
            back = [tuple(reversed(r)) for r in tg.routes[(b, a)]]
            assert back == rr[::-1]
    assert len(tg.link_speed) == (nx - 1) * ny + nx * (ny - 1)


def test_mesh_rates_and_degraded_chip():
    hw = HW(peak_flops=100.0, mfu=0.5)
    tg = tpu_mesh_topology(2, 2, hw, degraded={3: 0.8})
    assert list(tg.rates) == [50.0, 50.0, 50.0, 40.0]
    with pytest.raises(ValueError):
        tpu_mesh_topology(2, 2, hw, degraded={4: 0.5})


# -------------------------------------------------------- the placement
def _mesh_step(nx, ny, seed=1, hw=HW(), slow=0.8):
    cfg = ModelConfig(name="tiny-ep", family="moe", n_layers=4,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=96,
                      vocab=100, n_experts=nx * ny, top_k=2)
    tok, kv = step(cfg, L=2, M=2, D=nx * ny, chunk=8, seed=seed)
    tg = tpu_mesh_topology(nx, ny, hw, degraded={1: slow})
    return ep_step_graph(cfg, tok, kv, 2, tg), tg


def _placed(g, tg, backend):
    pl = plan_placement(g, tg, "hvlb_b", alpha_max=0.1, backend=backend)
    if backend == "pallas":
        assert pl.plan.backend == "pallas" and pl.plan.fallback is None
        assert pl.plan.replay.sims_full == 3        # one fused sweep
    return pl


@pytest.mark.parametrize("nx,ny", [(2, 2), (3, 3)])
def test_pallas_sweep_matches_scalar_on_mesh_f64(nx, ny):
    """The fused sweep on a multi-route, multi-hop mesh under the f64
    interpreter: the scalar engine's plan, decision for decision."""
    pytest.importorskip("jax")
    g, tg = _mesh_step(nx, ny)
    s, p = _placed(g, tg, "scalar"), _placed(g, tg, "pallas")
    assert list(p.plan.sweep.alphas) == list(s.plan.sweep.alphas)
    assert p.plan.best_alpha == s.plan.best_alpha
    assert np.array_equal(p.schedule.proc, s.schedule.proc)
    assert p.assignment == s.assignment
    np.testing.assert_allclose(p.plan.sweep.makespans,
                               s.plan.sweep.makespans, rtol=1e-12)
    np.testing.assert_allclose(p.schedule.finish, s.schedule.finish,
                               rtol=1e-12)
    for e, m in s.schedule.messages.items():
        assert p.schedule.messages[e].route == m.route
    # some message of the plan crossed the mesh on more than one hop
    assert max(len(m.route) for m in s.schedule.messages.values()) > 1


def test_pallas_sweep_matches_scalar_on_mesh_f32():
    """The same sweep under the chip's numerics (float32, tile-padded) on
    the 2x2 mesh: the scalar engine's decisions, every float within the
    near-tie band.  (Where values that are equal in exact arithmetic
    decide a route or the order of a task's messages, float32 may
    resolve them otherwise and the schedules part; DESIGN.md section 5.
    So the chips here run 1 FLOP a second, half that on chip 1, and the
    links carry 1 byte a second: every computation and message time is
    a whole number below 2**24, every sum of them exact in float32, and
    no such tie can round apart.)"""
    pytest.importorskip("jax")
    from repro.core.backends.pallas import F32_NEAR_TIE_RTOL

    mp = pytest.MonkeyPatch()
    mp.setenv("REPRO_PALLAS_DTYPE", "float32")
    mp.setenv("REPRO_PALLAS_TILE", "1")
    try:
        g, tg = _mesh_step(2, 2, hw=HW(peak_flops=2.0, mfu=0.5,
                                       ici_bw=1.0), slow=0.5)
        assert (g.comp_matrix == np.round(g.comp_matrix)).all()
        s, p = _placed(g, tg, "scalar"), _placed(g, tg, "pallas")
    finally:
        mp.undo()
    assert s.makespan_s < 2**24
    assert p.plan.best_alpha == s.plan.best_alpha
    assert np.array_equal(p.schedule.proc, s.schedule.proc)
    np.testing.assert_allclose(p.plan.sweep.makespans,
                               s.plan.sweep.makespans,
                               rtol=F32_NEAR_TIE_RTOL)
    np.testing.assert_allclose(p.schedule.finish, s.schedule.finish,
                               rtol=F32_NEAR_TIE_RTOL)
    for e, m in s.schedule.messages.items():
        assert p.schedule.messages[e].route == m.route


def test_placement_pays_for_leaving_home():
    """Each placed task takes its chip's computation time, streamed bytes
    included off home; where the weights outweigh the work, as in this
    small model, the plan keeps the tasks home that the same step with
    free migration scatters over the mesh."""
    from repro.core.graph import SPG

    g, tg = _mesh_step(3, 3)
    s = _placed(g, tg, "scalar").schedule
    np.testing.assert_allclose(s.finish - s.start,
                               g.comp_matrix[np.arange(g.n), s.proc],
                               rtol=1e-12)
    free = SPG(n=g.n, edges=list(g.edges), weights=g.weights,
               tpl=dict(g.tpl))
    scattered = _placed(free, tg, "scalar").schedule
    assert np.mean(s.proc != g.ep_home) < 0.1
    assert np.mean(scattered.proc != g.ep_home) > 0.5


def test_placement_plan_carries_the_plan():
    tok, kv = step()
    tg = tpu_mesh_topology(2, 2)
    g = ep_step_graph(TINY, tok, kv, 2, tg)
    pl = plan_placement(g, tg, "hvlb_b",
                        alpha_max=0.2, backend="scalar")
    assert len(pl.plan.sweep.alphas) == 5
    assert pl.plan.best_alpha in list(pl.plan.sweep.alphas)
    assert pl.makespan_s == pl.plan.makespan == pl.schedule.makespan
    assert pl.schedule is pl.plan.schedule


def test_scan_counts_hop_steps_and_ct_bytes():
    """``backend.hop_steps`` is padded alphas x waves x slots x
    predecessors x routes x hops of the dispatch; ``backend.ct_bytes``
    the edge CTML table's share of ``backend.h2d_bytes``, uploaded
    factored by the distinct hop speeds (``backend.ct_columns``)."""
    pytest.importorskip("jax")
    tok, kv = step()
    tg = tpu_mesh_topology(2, 2)
    g = ep_step_graph(TINY, tok, kv, 2, tg)
    tracing.reset()
    tracing.enable()
    try:
        plan_placement(g, tg, "hvlb_b", alpha_max=0.1, backend="pallas")
        c = tracing.snapshot()["counters"]
    finally:
        tracing.disable()
    # 3 alphas pad to 4; 2 layers x 2 microbatches of 4 + 4 tasks plan as
    # 5 waves of up to 8 (the microbatches interleave), padded to 8 x 8;
    # in-degree 4; 2 routes of up to 2 hops on a 2x2 mesh
    assert c["backend.hop_steps"] == 4 * 8 * 8 * 4 * 2 * 2
    # the factored upload: every link of the mesh runs at one speed, so
    # one quotient column, (Ep, P + 1, 1) float64 with 64 edges + 1 pad
    # bucketed to 128, and the (P + 1, R, H, P) int32 column index
    assert c["backend.ct_columns"] == 1
    assert c["backend.ct_bytes"] == 128 * 5 * 1 * 8 + 5 * 2 * 2 * 4 * 4
    assert c["backend.h2d_bytes"] > c["backend.ct_bytes"]
