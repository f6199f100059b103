"""Closed loop, one client: every request places a fresh MoE step.

Each request builds the program's step graph of one expert-parallel
prefill step (``repro.planner.ep_step_graph``) from a pool of routings
drawn from the seed, round-robin, and places it on the configuration's
mesh with ``repro.planner.plan_placement`` under HVLB_CC(B), as an
operator re-placing a step whose expert loads have moved.  The next
request starts when the plan is back.  On the device backend that is the
fused 101-alpha sweep: one dispatch per plan, then the host decode of
alpha*.

Traffic parameters (``bench/traffic/<name>.json``): ``pool`` steps.

``plans_per_s`` is the plans returned without error over the window,
which runs from the first request to the end of the first plan that
finishes at or after ``--seconds``.

With ``--trace 1`` the program's own recorder (``repro.tracing``) runs
over the window, and its counters and span totals are handed over in
``Outcome.counters``: ``h2d_bytes``, ``d2h_bytes``, ``hop_steps``,
``ct_bytes`` and ``tables_s`` (``repro.backend.build`` + ``.tables``),
each only where the program records it.  ``sims`` (each plan's
``Plan.replay``, full + resumed) is handed over in every run.

Set-up is the draws and one plan per padded scan shape of the pool,
found from the program's own queue; the benchmark's graphs and mesh are
built after the window.  The check: each served plan's graph against the
benchmark's own (``benchlib.ep_graph``: the same edges, and weights,
computation times and volumes within 1e-12 relative, the same task
labels) and the program's mesh against the benchmark's; each plan's
alpha* walked by the reference with its decisions forced; each served
pool graph's sweep at alpha 0, the grid's last alpha,
``check.sampled_alphas`` grid points drawn from the seed before the
window, and each alpha* its plans chose; and the faults the submit cell
checks.  Plans with identical answers are walked once.
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from benchlib import ep_graph as EG
from benchlib import reference as R
from benchlib import roofline
from benchlib.cell import Context, Outcome

from loads import program
from loads.submit_closed import _bucket

GRAPH_RTOL = 1e-12
# program counters and span totals handed to the per-layer readers
COUNTERS = {"h2d_bytes": "backend.h2d_bytes",
            "d2h_bytes": "backend.d2h_bytes",
            "hop_steps": "backend.hop_steps",
            "ct_bytes": "backend.ct_bytes"}
SPANS = {"tables_s": ("repro.backend.build", "repro.backend.tables")}


def data(cfg, tr, seed):
    """The pool's draws and the grid indices the check samples, all
    from ``seed``."""
    rng = np.random.default_rng(seed % 2**64)
    draws = [EG.draw_step(rng, cfg["model"], cfg["step"])
             for _ in range(tr["pool"])]
    grid = program.alpha_grid(cfg["policy"])
    inner = np.arange(1, len(grid) - 1)
    k = min(cfg["check"]["sampled_alphas"], len(inner))
    sampled = sorted({0, len(grid) - 1} |
                     {int(a) for a in rng.choice(inner, k, replace=False)})
    return draws, sampled


def reference(cfg, draws):
    """The benchmark's mesh and its graph of each step."""
    c = EG.mesh_cluster(cfg["cluster"])
    return c, [EG.step_graph(cfg["model"], *d, c) for d in draws]


def control_cases(cfg, tr, seed):
    """(prepared graph, alphas) of each pool graph at the alphas the
    check samples before the window."""
    draws, sampled = data(cfg, tr, seed)
    c, graphs = reference(cfg, draws)
    grid = program.alpha_grid(cfg["policy"])
    return [(R.prepare(g, c), [grid[a] for a in sampled])
            for g, _ in graphs]


def _fingerprint(n, edges, tpl, weights, comp, labels) -> tuple:
    """What the check compares of a graph, kept as arrays: its edges
    sorted, their volumes, the weights, the computation times, and the
    task labels."""
    e = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    e = e[np.lexsort((e[:, 1], e[:, 0]))]
    vols = np.array([tpl[(int(i), int(j))] for i, j in e])
    return (n, e, vols, np.array(weights, dtype=float),
            np.array(comp, dtype=float), list(labels))


def _graph_faults(got: tuple, ref: tuple) -> List[str]:
    n, edges, vols, weights, comp, labels = got
    rn, redges, rvols, rweights, rcomp, rlabels = ref
    if n != rn:
        return [f"{n} tasks, the reference has {rn}"]
    if edges.shape != redges.shape or (edges != redges).any():
        return ["edges differ from the reference's"]
    if comp.shape != rcomp.shape:
        return [f"computation times of shape {comp.shape}, the "
                f"reference's {rcomp.shape}"]
    out = []
    for what, a, b in (("edge volume", vols, rvols),
                       ("task weight", weights, rweights),
                       ("computation time", comp.ravel(), rcomp.ravel())):
        off = np.abs(a - b) > GRAPH_RTOL * np.abs(b)
        if off.any():
            k = int(np.argmax(off))
            out.append(f"{what} {k} is {a[k]!r}, the reference's {b[k]!r}")
    if labels != rlabels:
        out.append("task labels differ from the reference's")
    return out


def _mesh_faults(tg, c: R.Cluster) -> List[str]:
    out = []
    rates = np.asarray(tg.rates, dtype=float)
    if len(rates) != c.n_procs or not np.all(
            np.abs(rates - c.rates) <= GRAPH_RTOL * np.abs(c.rates)):
        out.append("processor rates differ from the reference's")
    if dict(tg.link_speed) != c.link_speed:
        out.append("links differ from the reference's")
    got = {k: [tuple(r) for r in v] for k, v in tg.routes.items()}
    if got != c.routes:
        out.append("routes differ from the reference's")
    return out


def run(ctx: Context) -> Outcome:
    # the program's planner first: a tree without it fails here, at once
    from repro import planner
    missing = [f for f in ("ep_step_graph", "tpu_mesh_topology")
               if not hasattr(planner, f)]
    if missing:
        raise ImportError(f"repro.planner has no {', '.join(missing)}: "
                          f"this program cannot build the cell's step")
    from repro.configs import ARCHS
    from repro.core.ranks import hprv_b, priority_queue, rank_matrix
    from repro.planner.cost_model import HW

    import repro.core as core
    import repro.core.backends.pallas as pb
    from repro.core.engine import DEFAULT_BATCH_MAX, plan_waves

    cfg, tr = ctx.config, ctx.traffic
    pol, cl, st = cfg["policy"], cfg["cluster"], cfg["step"]
    if pol["name"] != "HVLB_CC_B" or pol["alpha_step"] != 0.05:
        raise ValueError(f"plan_placement sweeps HVLB_CC(B) in steps of "
                         f"0.05, not {pol}")
    model = ARCHS[cfg["model"]["registry"]]
    draws, sampled = data(cfg, tr, ctx.seed)
    grid = program.alpha_grid(pol)
    hw = HW(peak_flops=cl["peak_flops"], mfu=cl["mfu"],
            ici_bw=cl["link_bw"])
    tg = planner.tpu_mesh_topology(
        cl["nx"], cl["ny"], hw,
        degraded={int(p): f for p, f in cl.get("degraded", {}).items()})
    backend = cfg["backend"]
    precision = cfg["check"]["precision"]

    def place(k: int):
        g = planner.ep_step_graph(model, *draws[k], st["microbatches"], tg)
        pl = planner.plan_placement(g, tg, "hvlb_b",
                                    alpha_max=pol["alpha_max"],
                                    backend=backend)
        return g, pl

    probe = ctx.probe
    probe.count(pb.PallasBackend, "n_launches", "launches")
    probe.wrap(planner, "ep_step_graph", "bench.planner.ep_graph")
    probe.wrap(core.Scheduler, "submit", "bench.api.submit")
    probe.wrap(pb.PallasBackend, "_scan_dispatch", "bench.backend.dispatch")
    probe.wrap(pb.PallasBackend, "_decode_scan", "bench.engine.decode")
    rt = None
    if probe.on:
        from repro import tracing as rt

    # warm-up: one whole plan per padded scan shape of the pool, the
    # shapes read off the program's HVLB_CC(B) queue of each step
    t_warm = time.perf_counter()
    shapes: Dict[tuple, int] = {}
    for k, d in enumerate(draws):
        g = planner.ep_step_graph(model, *d, st["microbatches"], tg)
        r = rank_matrix(g, tg)
        waves = plan_waves(priority_queue(hprv_b(g, tg, r), r.mean(axis=1)),
                           g.pred, DEFAULT_BATCH_MAX)
        key = (_bucket(len(waves)), _bucket(max(len(w) for w in waves)),
               _bucket(len(g.edges) + 1), max(len(p) for p in g.pred))
        shapes.setdefault(key, k)
    for k in shapes.values():
        place(k)
    ctx.log(f"warm-up: seconds={time.perf_counter() - t_warm:.3f} "
            f"plans={len(shapes)} shapes={sorted(shapes)}")

    records: List[Tuple[int, Optional[dict], Optional[tuple]]] = []
    took = []                     # seconds per request
    failed = 0
    snap: Dict[str, Any] = {}
    t_start = ctx.open_window()
    if rt is not None:
        rt.reset()
        rt.enable()
    deadline = t_end = t_start + ctx.seconds
    k = 0
    while True:
        t0 = t_end if k else t_start
        i = k % len(draws)
        rec = fp = None
        try:
            g, pl = place(i)
            p = pl.plan
            s = p.schedule
            rec = {"backend": p.backend, "fallback": p.fallback,
                   "alphas": list(p.sweep.alphas),
                   "sweep": np.array(p.sweep.makespans, dtype=float),
                   "best_alpha": p.best_alpha, "makespan": p.makespan,
                   "placed": pl.makespan_s,
                   "sims": p.replay.sims_full + p.replay.sims_resumed,
                   "proc": np.array(s.proc), "start": np.array(s.start),
                   "finish": np.array(s.finish)}
            fp = _fingerprint(g.n, g.edges, g.tpl, g.weights, g.comp_matrix,
                              [g.ep_tasks[t] for t in range(g.n)])
            del g, pl, p, s
        except Exception as e:    # a failed request is counted, not fatal
            failed += 1
            ctx.checker.fault(f"request {k}: {type(e).__name__}: {e}")
        records.append((i, rec, fp))
        k += 1
        t_end = time.perf_counter()
        took.append(t_end - t0)
        if t_end >= deadline:
            break
    if rt is not None:
        snap = rt.snapshot()
        rt.disable()
    ctx.close_window()
    window_s = t_end - t_start
    took.sort()
    ctx.log(f"requests: seconds min {took[0]:.3f} median "
            f"{took[len(took) // 2]:.3f} max {took[-1]:.3f}")

    c, graphs = reference(cfg, draws)
    ok = [(i, r) for i, r, _ in records if r is not None]
    ops = nbytes = 0.0
    for i, r in ok:
        o, b = roofline.work(graphs[i][0], c, len(r["alphas"]))
        ops, nbytes = ops + o, nbytes + b
    counters: Dict[str, float] = {"sims": float(sum(r["sims"]
                                                    for _, r in ok))}
    for key, name in COUNTERS.items():
        if name in snap.get("counters", {}):
            counters[key] = float(snap["counters"][name])
    for key, names in SPANS.items():
        got = [snap["spans"][s]["total_s"] for s in names
               if s in snap.get("spans", {})]
        if got:
            counters[key] = float(sum(got))

    def check() -> None:
        chk = ctx.checker
        t = time.perf_counter()
        preps = [R.prepare(g, c) for g, _ in graphs]
        ref_fp = [_fingerprint(g.n, g.edges, g.tpl, g.weights,
                               g.comp_matrix, labels)
                  for g, labels in graphs]
        for f in _mesh_faults(tg, c):
            chk.fault(f"mesh: {f}")
        jobs: List[tuple] = []
        forced: Dict[tuple, List[int]] = {}     # job key -> plans
        picks: Dict[int, set] = {}
        off_home = []
        for n, (i, r, fp) in enumerate(records):
            if r is None:
                continue
            what = f"plan {n} (pool graph {i})"
            for f in _graph_faults(fp, ref_fp[i]):
                chk.fault(f"{what}: graph: {f}")
            if r["backend"] != backend or r["fallback"] is not None:
                chk.fault(f"{what}: backend {r['backend']}, fallback "
                          f"{r['fallback']}")
            if r["alphas"] != grid:
                chk.fault(f"{what}: alphas are not the policy's grid")
                continue
            sweep = r["sweep"]
            best = 0            # the policy's rule: strict improvement
            for a in range(1, len(sweep)):
                if sweep[a] < sweep[best] - 1e-12:
                    best = a
            if r["best_alpha"] != grid[best]:
                chk.fault(f"{what}: chose alpha {r['best_alpha']}, its "
                          f"own sweep is best at {grid[best]}")
            if not (sweep[best] == r["makespan"] == r["placed"]
                    == float(np.max(r["finish"]))):
                chk.fault(f"{what}: makespan {r['makespan']} vs sweep "
                          f"{sweep[best]} vs placement {r['placed']} vs "
                          f"schedule {float(np.max(r['finish']))}")
            picks.setdefault(i, set()).add(best)
            key = (i, best, r["proc"].tobytes(), r["start"].tobytes(),
                   r["finish"].tobytes(), r["makespan"])
            if key not in forced:
                forced[key] = []
                homes = [lab[3] % c.n_procs for lab in ref_fp[i][5]]
                off_home.append(float(np.mean(r["proc"] != homes)))
                jobs.append((i, precision, chk.limits, grid[best],
                             r["proc"], r["start"], r["finish"],
                             r["makespan"], what))
            forced[key].append(n)
        at = {i: sorted(set(sampled) | picks[i]) for i in sorted(picks)}
        sweeps = [(i, grid[a]) for i in sorted(at) for a in at[i]]
        # how far the stated precision moves alpha*'s schedule from the
        # float64 reference's: logged, not compared
        stars = [(i, grid[a]) for i in sorted(picks)
                 for a in sorted(picks[i])]
        workers = min(8, os.cpu_count() or 1)
        t_prep = time.perf_counter() - t
        got = EG.forced_walks(preps, jobs, min(workers, len(jobs) // 2))
        ref = iter(EG.sweep_makespans(preps, sweeps, precision, workers))
        f64 = iter(EG.sweep_makespans(preps, stars, "float64", workers))
        ctx.log(f"reference ({precision}): {len(jobs)} forced walks for "
                f"{sum(map(len, forced.values()))} plans, {len(sweeps)} "
                f"sweep walks, {len(stars)} float64 walks, at most {workers} "
                f"processes, seconds={time.perf_counter() - t:.3f} "
                f"(prepare {t_prep:.3f})")
        if off_home:
            ctx.log(f"tasks off their home chip at alpha*: share min "
                    f"{min(off_home):.3f} max {max(off_home):.3f}")
        for (worst, checked, faults), plans in zip(got, forced.values()):
            for name, v in worst.items():
                chk.worst[name] = max(chk.worst[name], v)
            chk.checked += checked * len(plans)
            for f in faults:
                chk.fault(f)
        ref_ms = {i: {a: next(ref) for a in at[i]} for i in sorted(at)}
        f64_ms = {(i, a): next(f64)
                  for i in sorted(picks) for a in sorted(picks[i])}
        drift = 0.0
        for n, (i, r, _) in enumerate(records):
            if r is None or r["alphas"] != grid:
                continue
            chk.sweep([ref_ms[i][a] for a in at[i]],
                      [r["sweep"][a] for a in at[i]],
                      r["makespan"], f"plan {n} (pool graph {i})")
            a = grid.index(r["best_alpha"])
            drift = max(drift, abs(r["makespan"] - f64_ms[(i, a)])
                        / f64_ms[(i, a)])
        ctx.log(f"float64 reference: worst makespan difference at alpha* "
                f"{drift!r}")

    return Outcome(window_start=t_start, window_s=window_s,
                   attempted=len(records), failed=failed,
                   end_to_end={"plans_per_s": len(ok) / window_s},
                   plans=len(ok), counters=counters,
                   work=[ops, nbytes], check=check)
