"""Closed loop, one client: every request is a fresh plan.

Each request builds a new ``Scheduler`` on the configuration's cluster
and submits one graph, as a user with a new task set does; the graph
comes round-robin from a pool drawn from the seed.  The next request
starts when the plan is back.  Under a grid policy this is the fused
(alpha, wave) sweep: one device dispatch per plan, then the host decode
of every alpha's schedule.

Traffic parameters (``bench/traffic/<name>.json``): ``pool`` graphs.

``plans_per_s`` is the plans returned without error over the window,
which runs from the first request to the end of the first plan that
finishes at or after ``--seconds``: all the work, all the time.
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchlib import reference as R
from benchlib import roofline
from benchlib.cell import Context, Outcome

from loads import program


def _bucket(b: int) -> int:
    n = 1
    while n < b:
        n *= 2
    return n


def _shapes(pool_ref, c, cap: int, plan_waves) -> dict:
    """One pool graph for each padded scan shape the pool's plans take:
    the wave plan (``engine.plan_waves`` on the reference's queue) padded
    to powers of two in waves and width, with the edge count and the
    largest in-degree, which size the scan's other inputs."""
    first = {}
    for k, g in enumerate(pool_ref):
        waves = plan_waves(R.prepare(g, c).queue, g.pred, cap)
        key = (_bucket(len(waves)), _bucket(max(len(w) for w in waves)),
               _bucket(len(g.edges) + 1), max(len(p) for p in g.pred))
        first.setdefault(key, k)
    return first


def data(cfg, tr, seed):
    """The reference cluster and the pool of graphs drawn from ``seed``."""
    c = R.switched_cluster(*program.cluster_params(cfg["cluster"]))
    rng = np.random.default_rng(seed % 2**64)
    return c, program.graphs(rng, c, cfg["graphs"], tr["pool"])


def control_cases(cfg, tr, seed):
    """(prepared graph, alpha grid) of each plan a run can serve."""
    c, pool = data(cfg, tr, seed)
    grid = program.alpha_grid(cfg["policy"])
    return [(R.prepare(g, c), grid) for g in pool]


def run(ctx: Context) -> Outcome:
    import repro.core as core
    import repro.core.backends.pallas as pb
    from repro.core.engine import DEFAULT_BATCH_MAX, plan_waves

    cfg, tr = ctx.config, ctx.traffic
    c, pool_ref = data(cfg, tr, ctx.seed)
    tg = program.topology(core, cfg["cluster"])
    pool = [program.spg(core, g, f"pool{k}")
            for k, g in enumerate(pool_ref)]
    pol = program.policy(core, cfg["policy"])
    grid = program.alpha_grid(cfg["policy"])
    backend = cfg["backend"]

    def submit(g):
        return core.Scheduler(tg, policy=pol, backend=backend).submit(g)

    probe = ctx.probe
    probe.count(pb.PallasBackend, "n_launches", "launches")
    probe.wrap(core.Scheduler, "submit", "bench.api.submit")
    probe.wrap(pb.PallasBackend, "_scan_dispatch", "bench.backend.dispatch")
    probe.wrap(pb.PallasBackend, "_decode_scan", "bench.engine.decode")

    # warm-up: one whole plan per padded scan shape of the pool
    t_warm = time.perf_counter()
    shapes = _shapes(pool_ref, c, DEFAULT_BATCH_MAX, plan_waves)
    for k in shapes.values():
        submit(pool[k])
    ctx.log(f"warm-up: seconds={time.perf_counter() - t_warm:.3f} "
            f"plans={len(shapes)} shapes={sorted(shapes)}")

    records = []                  # (pool index, plan or None)
    took = []                     # seconds per request
    failed = 0
    t_start = ctx.open_window()
    deadline = t_end = t_start + ctx.seconds
    k = 0
    while True:
        t0 = t_end if k else t_start
        try:
            plan = submit(pool[k % len(pool)])
        except Exception as e:    # a failed request is counted, not fatal
            plan = None
            failed += 1
            ctx.checker.fault(f"request {k}: {type(e).__name__}: {e}")
        records.append((k % len(pool), plan))
        k += 1
        t_end = time.perf_counter()
        took.append(t_end - t0)
        if t_end >= deadline:
            break
    ctx.close_window()
    window_s = t_end - t_start
    took.sort()
    ctx.log(f"requests: seconds min {took[0]:.3f} median "
            f"{took[len(took) // 2]:.3f} max {took[-1]:.3f}")

    ok = [(i, p) for i, p in records if p is not None]
    ops = nbytes = 0.0
    sims = 0
    for i, p in ok:
        o, b = roofline.work(pool_ref[i], c, len(p.sweep.alphas))
        ops, nbytes = ops + o, nbytes + b
        sims += p.replay.sims_full + p.replay.sims_resumed

    def check() -> None:
        chk = ctx.checker
        served = sorted({i for i, p in records if p is not None})
        preps = [R.prepare(pool_ref[i], c) for i in served]
        # the reference's own schedule at every alpha of the grid, for
        # each pool graph served
        jobs = [(k, a) for k in range(len(served)) for a in grid]
        ms = [w[0] for w in R.walk_many(
            preps, jobs, workers=min(8, os.cpu_count() or 1))]
        ref_ms = {i: ms[k * len(grid):(k + 1) * len(grid)]
                  for k, i in enumerate(served)}
        for n, (i, p) in enumerate(records):
            if p is None:
                continue
            what = f"plan {n} (pool graph {i})"
            if p.backend != backend or p.fallback is not None:
                chk.fault(f"{what}: backend {p.backend}, fallback "
                          f"{p.fallback}")
            alphas = list(p.sweep.alphas)
            if alphas != grid:
                chk.fault(f"{what}: alphas are not the policy's grid")
                continue
            sweep = list(p.sweep.makespans)
            best = 0            # the policy's rule: strict improvement
            for a in range(1, len(sweep)):
                if sweep[a] < sweep[best] - 1e-12:
                    best = a
            if p.best_alpha != grid[best]:
                chk.fault(f"{what}: chose alpha {p.best_alpha}, its own "
                          f"sweep is best at {grid[best]}")
            if not (sweep[best] == p.makespan == float(
                    np.max(p.schedule.finish))):
                chk.fault(f"{what}: makespan {p.makespan} vs sweep "
                          f"{sweep[best]} vs schedule "
                          f"{float(np.max(p.schedule.finish))}")
            chk.sweep(ref_ms[i], sweep, p.makespan, what)
            chk.schedule(preps[served.index(i)], p.best_alpha,
                         p.schedule.proc, p.schedule.start,
                         p.schedule.finish, p.makespan, what)

    return Outcome(window_start=t_start, window_s=window_s,
                   attempted=len(records), failed=failed,
                   end_to_end={"plans_per_s": len(ok) / window_s},
                   plans=len(ok),
                   counters={"sims": float(sims)},
                   work=[ops, nbytes], check=check)
