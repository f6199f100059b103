#!/usr/bin/env python3
"""Smoke run of the scheduler's device path on one TPU chip.

    python chip_smoke.py

Drives the HVLB_CC device path (the ``pallas`` backend's compiled
float32 ``lax.scan``) once through the entry points a user calls, at
the largest deployment the repo supports, and holds every result to the
scalar reference under the f32 near-tie policy:

0. preconditions: no environment knob that steers the device path is
   set, JAX sees a TPU, and a backend demotion raises (``RuntimeWarning``
   is an error);
1. the paper example under HSV_CC, HVLB_CC(B) and HVLB_CC_IC through
   ``Scheduler``;
2. exp7's largest cell (P=16 fully switched, n=500) under HVLB_CC(B),
   alpha 0..5 step 0.05: 101 alphas in one fused dispatch, then a
   processor failure and its replan on the same session;
3. exp6's multi-hop placement (qwen3-8b pipeline, 8 microbatches, on 8
   TPU slices in 2 pods) through ``planner.plan_placement``;
4. ``SchedulerService`` behind its TCP front end: one pipelined burst
   from 2 tenants (register 4 graphs each, plan, update, plan).

Every plan must come from the pallas backend with no fallback, and every
device backend must have run compiled (no interpreter, f32, tile
padding).  The lines before the last give each phase's set-up (its
first device calls, compilation included) apart from a warm repeat, the
padded scan shapes compiled and the plan counts.  The last line is one
JSON object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
The script exits nonzero, printing no result, on the first failed check
or when JAX finds no TPU.
"""
from __future__ import annotations

import asyncio
import json
import os
import sys
import time
import warnings
from typing import NoReturn, Optional

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Environment knobs that steer the device path away from its default
# (interpreter, dtype, tiling, per-wave loop, forced backend, watchdog).
STEERING_ENV = ("REPRO_PALLAS_INTERPRET", "REPRO_PALLAS_DTYPE",
                "REPRO_PALLAS_TILE", "REPRO_PALLAS_SCAN",
                "REPRO_SCHED_BACKEND", "REPRO_SCHED_WAVE_TIMEOUT")

P16_SWEEP = dict(alpha_max=5.0, alpha_step=0.05)     # exp7's sweep grid


class SmokeFailure(Exception):
    """A phase check failed."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ------------------------------------------------------------- device log
class DeviceLog:
    """Every ``PallasBackend`` this process builds and every padded scan
    shape it compiles (wrappers around the backend's constructor and
    its runner cache)."""

    def __init__(self, pb) -> None:
        self.backends: list = []
        self.shapes: list = []
        init, scan_run = pb.PallasBackend.__init__, pb._scan_run

        def tracked_init(be, inst):
            init(be, inst)
            self.backends.append(be)

        def tracked_scan_run(*key):
            if ("scan",) + key not in pb._RUN_CACHE:
                self.shapes.append(key)
            return scan_run(*key)

        pb.PallasBackend.__init__ = tracked_init
        pb._scan_run = tracked_scan_run

    def counters(self) -> tuple:
        return (sum(be.n_launches for be in self.backends),
                sum(be.n_roundtrips for be in self.backends))

    def check_compiled(self) -> None:
        check(bool(self.backends), "no pallas backend was built")
        for be in self.backends:
            check(not be._interpret and be._f32 and be._tile,
                  f"a pallas backend ran interpreted={be._interpret} "
                  f"f32={be._f32} tile={be._tile}, not compiled f32 tiled")


def check_plan(plan, what: str) -> None:
    check(plan.backend == "pallas",
          f"{what}: plan came from backend {plan.backend!r}, not pallas")
    check(plan.fallback is None, f"{what}: plan fell back {plan.fallback}")


# ------------------------------------------------------ near-tie policy
def _value(be, j: int, p: int, eft: float) -> float:
    """Def. 4.2 selection value of candidate ``p`` for task ``j``,
    in the scalar backend's own operation order."""
    inst = be.inst
    if inst._is_exit[j]:
        return eft
    return eft * inst._ldet[j][p] * (
        1.0 + (be.loads[p] / be.period) * be.alpha)


def _candidate_eft(be, j: int, p: int) -> float:
    """The scalar reference's EFT of placing ``j`` on ``p`` (every
    other processor priced out for this one evaluation)."""
    inst = be.inst
    row = inst._comp[j]
    inst._comp[j] = [c if q == p else 1e300 for q, c in enumerate(row)]
    try:
        d = be.evaluate(j)
    finally:
        inst._comp[j] = row
    check(d[0] == p, f"could not price candidate p{p} of task {j}")
    return d[2]


def first_divergence_gap(ref_sess, queue, alpha: float, period: float,
                         dev_proc) -> float:
    """Walk ``queue`` with the scalar reference while its winners agree
    with ``dev_proc``; at the first disagreement return how much worse
    the device's pick is, relative to the reference winner's selection
    value (the near-tie band is ``F32_NEAR_TIE_RTOL``)."""
    be = ref_sess.inst.backend_instance("scalar")
    be.start(alpha, period, False)
    for j in queue:
        d = be.evaluate(j)
        p = int(dev_proc[j])
        if d[0] != p:
            v_ref = _value(be, j, d[0], d[2])
            v_dev = _value(be, j, p, _candidate_eft(be, j, p))
            return (v_dev - v_ref) / abs(v_ref)
        be.apply(j, d[0], d[1], d[2], d[3])
    raise SmokeFailure("device and reference placements differ, but the "
                       "reference walk reproduced every device decision")


class NearTie:
    """Holds device results to the scalar reference: decision-identical
    with floats within ``rtol``, or the first differing decision is a
    verified near tie (its selection values within ``rtol``), after
    which the schedules may legitimately part."""

    def __init__(self, rtol: float) -> None:
        self.rtol = rtol
        self.flips = 0

    def schedule(self, dev_proc, dev_finish, ref_s, ref_sess, queue,
                 alpha: float, period: float, what: str) -> bool:
        """True when decision-identical; False after a verified flip."""
        if np.array_equal(dev_proc, ref_s.proc):
            check(np.allclose(dev_finish, ref_s.finish, rtol=self.rtol,
                              atol=0.0),
                  f"{what}: same decisions, but finish times differ by "
                  f"more than rtol={self.rtol}")
            return True
        gap = first_divergence_gap(ref_sess, queue, alpha, period,
                                   dev_proc)
        check(gap <= self.rtol,
              f"{what}: decisions differ at alpha={alpha}, and the first "
              f"differing decision is no near tie (gap {gap:.3g})")
        self.flips += 1
        return False

    def best(self, dev_alpha: float, dev_proc, dev_finish, ref_plan,
             ref_sess, queue, what: str) -> None:
        """The device's chosen schedule: decided as the reference would
        at its alpha, and that alpha's reference makespan is within the
        band of the reference optimum (or a verified flip explains it)."""
        period = ref_plan.period
        alphas = ref_plan.sweep.alphas
        k = int(np.argmin(np.abs(alphas - dev_alpha)))
        check(abs(alphas[k] - dev_alpha) < 1e-12,
              f"{what}: device alpha {dev_alpha} is not on the grid")
        ref_s = ref_sess.inst.schedule(queue, dev_alpha, period,
                                       backend="scalar")
        if self.schedule(dev_proc, dev_finish, ref_s, ref_sess, queue,
                         dev_alpha, period, what):
            best = float(ref_plan.sweep.makespans.min())
            check(ref_plan.sweep.makespans[k] <= best * (1 + self.rtol),
                  f"{what}: device picked alpha={dev_alpha} (reference "
                  f"makespan {ref_plan.sweep.makespans[k]}), reference "
                  f"optimum {best} at alpha={ref_plan.sweep.best_alpha}")

    def sweep(self, dev_plan, ref_plan, dev_sess, ref_sess, queue,
              what: str) -> None:
        """Same alphas; each alpha's makespan within the band, or the
        device schedule at that alpha is explained by a near tie."""
        check(np.array_equal(dev_plan.sweep.alphas, ref_plan.sweep.alphas),
              f"{what}: alpha grids differ")
        period = ref_plan.period
        for alpha, md, mr in zip(ref_plan.sweep.alphas,
                                 dev_plan.sweep.makespans,
                                 ref_plan.sweep.makespans):
            if abs(md - mr) <= self.rtol * abs(mr):
                continue
            dev_s = dev_sess.inst.schedule(queue, alpha, period,
                                           backend="pallas")
            ref_s = ref_sess.inst.schedule(queue, alpha, period,
                                           backend="scalar")
            check(not self.schedule(dev_s.proc, dev_s.finish, ref_s,
                                    ref_sess, queue, alpha, period, what),
                  f"{what}: makespan {md} vs {mr} at alpha={alpha}")
        self.best(dev_plan.best_alpha, dev_plan.schedule.proc,
                  dev_plan.schedule.finish, ref_plan, ref_sess, queue, what)


def session_queue(sched, policy) -> tuple:
    """The last-submitted session of ``sched`` and its priority queue."""
    sess = sched._last
    return sess, sess.queue_for(sched.topology, policy)


# ----------------------------------------------------------------- phases
class Smoke:
    def __init__(self, log: DeviceLog, rtol: float) -> None:
        self.log = log
        self.tie = NearTie(rtol)
        self.rtol = rtol
        self.plans = 0

    def timed(self, fn):
        """(result, wall seconds, scan shapes compiled during the call)."""
        n0 = len(self.log.shapes)
        t0 = time.perf_counter()
        out = fn()
        return out, time.perf_counter() - t0, self.log.shapes[n0:]

    def report(self, name: str, setup: float, warm: Optional[float],
               ref: float, shapes: list, plans: int,
               extra: str = "") -> None:
        """``warm`` is None for a call that is not repeated."""
        self.plans += plans
        warm_s = "not_repeated" if warm is None else f"{warm:.3f}"
        print(f"phase {name}: setup_s={setup:.3f} (first device calls, "
              f"compile included) warm_s={warm_s} reference_s={ref:.3f} "
              f"plans={plans} compiled={len(shapes)}{extra}", flush=True)
        for key in shapes:
            print(f"  scan shape W,B,K,R,H,Pp,Lp,Np,Ep,A={key[:10]}",
                  flush=True)

    # -- 1 ------------------------------------------------------------
    def paper(self) -> None:
        from repro.core import (HSV_CC, HVLB_CC_B, HVLB_CC_IC, Scheduler,
                                paper_spg, paper_topology)

        g, tg = paper_spg(), paper_topology()
        policies = (HSV_CC(), HVLB_CC_B(alpha_max=3.0, period=150.0),
                    HVLB_CC_IC(alpha_max=3.0, period=150.0))

        def device():
            sched = Scheduler(tg, backend="pallas")
            return sched, [sched.submit(g, pol) for pol in policies]

        (dev, plans), setup, shapes = self.timed(device)
        (_, warm_plans), warm, again = self.timed(device)
        check(not again, f"paper: warm repeat compiled {again}")
        t0 = time.perf_counter()
        ref = Scheduler(tg, backend="scalar")
        refs = [ref.submit(g, pol) for pol in policies]
        ref_s = time.perf_counter() - t0
        for pol, plan, wplan, rplan in zip(policies, plans, warm_plans,
                                           refs):
            what = f"paper {type(pol).__name__}"
            for p in (plan, wplan):
                check_plan(p, what)
            rsess, queue = session_queue(ref, pol)
            if rplan.sweep is None:
                self.tie.schedule(plan.schedule.proc, plan.schedule.finish,
                                  rplan.schedule, rsess, queue, 0.0,
                                  rplan.period, what)
            else:
                dsess, _ = session_queue(dev, pol)
                self.tie.sweep(plan, rplan, dsess, rsess, queue, what)
            check(np.array_equal(plan.schedule.proc, wplan.schedule.proc),
                  f"{what}: warm repeat decided differently")
        hsv, hvlb, ic = plans
        check(ic.holes is not None, "paper IC: no schedule holes")
        self.report("1 paper", setup, warm, ref_s, shapes, 2 * len(plans),
                    f" makespans hsv={hsv.makespan} hvlb_b={hvlb.makespan}"
                    f"@alpha={hvlb.best_alpha} ic={ic.makespan}"
                    f"@alpha={ic.best_alpha}")

    # -- 2 ------------------------------------------------------------
    def exp7_cell(self) -> None:
        from repro.core import (HVLB_CC_B, Scheduler,
                                fully_switched_topology, random_spg,
                                schedule_violations)

        P, n = 16, 500
        rng = np.random.default_rng(77)                 # exp7's seeds
        tg = fully_switched_topology(
            P, rates=rng.uniform(0.6, 1.2, size=P),
            link_speeds=rng.uniform(0.5, 3.0, size=P))
        g = random_spg(n, np.random.default_rng(7000 + n + P), ccr=1.0,
                       tg=tg, max_in=3, max_out=6)
        pol = HVLB_CC_B(**P16_SWEEP)

        def device():
            sched = Scheduler(tg, policy=pol, backend="pallas")
            c0 = self.log.counters()
            plan = sched.submit(g)
            c1 = self.log.counters()
            return sched, plan, (c1[0] - c0[0], c1[1] - c0[1])

        (dev, plan, (launches, trips)), setup, shapes = self.timed(device)
        (_, wplan, wcount), warm, again = self.timed(device)
        check(not again, f"exp7: warm repeat compiled {again}")
        for p in (plan, wplan):
            check_plan(p, "exp7 P16 n500 sweep")
        check(len(plan.sweep.alphas) == 101, "exp7: expected 101 alphas")
        check((launches, trips) == (1, 1) and wcount == (1, 1),
              f"exp7: fused sweep took launches/round trips "
              f"{(launches, trips)} then {wcount}, expected one each")
        t0 = time.perf_counter()
        ref = Scheduler(tg, policy=pol, backend="scalar")
        rplan = ref.submit(g)
        ref_s = time.perf_counter() - t0
        rsess, queue = session_queue(ref, pol)
        dsess, _ = session_queue(dev, pol)
        self.tie.sweep(plan, rplan, dsess, rsess, queue, "exp7 sweep")
        bad = schedule_violations(plan.schedule, rtol=self.rtol)
        check(not bad, f"exp7: violations {bad[:3]}")
        self.report("2 exp7 P16 n500 (101 alphas, one dispatch)", setup,
                    warm, ref_s, shapes, 2,
                    f" makespan={plan.makespan}@alpha={plan.best_alpha} "
                    f"reference={rplan.makespan}@alpha={rplan.best_alpha} "
                    f"launches={launches} round_trips={trips}")

        # a processor failure on the same session: the busiest one
        proc = int(np.bincount(plan.schedule.proc, minlength=P).argmax())
        (fplan, fail_s, fshapes) = self.timed(
            lambda: dev.mark_failed(proc=proc))
        check_plan(fplan, "exp7 replan after proc failure")
        bad = schedule_violations(fplan.schedule, dev.faults,
                                  rtol=self.rtol)
        check(not bad, f"exp7 fault replan: violations {bad[:3]}")
        t0 = time.perf_counter()
        rfplan = ref.mark_failed(proc=proc)
        rfail_s = time.perf_counter() - t0
        rsess, queue = session_queue(ref, pol)
        dsess, _ = session_queue(dev, pol)
        self.tie.sweep(fplan, rfplan, dsess, rsess, queue,
                       "exp7 fault replan")
        self.report(f"2 exp7 mark_failed(proc={proc}) replan", fail_s, None,
                    rfail_s, fshapes, 1,
                    f" makespan={fplan.makespan}@alpha={fplan.best_alpha}"
                    f" invalidated={fplan.replay.invalidated_by_fault}")

    # -- 3 ------------------------------------------------------------
    def placement(self) -> None:
        from repro.configs import ARCHS, SHAPES
        from repro.core import HVLB_CC_B, Scheduler
        from repro.planner import (pipeline_graph, plan_placement,
                                   tpu_slice_topology)

        g = pipeline_graph(ARCHS["qwen3-8b"], SHAPES["train_4k"],
                           n_microbatches=8)
        tg = tpu_slice_topology(8, 32, pods=2)

        def device():
            c0 = self.log.counters()
            out = plan_placement(g, tg, "hvlb_b", backend="pallas")
            check(self.log.counters()[0] > c0[0],
                  "placement: no device launch")
            return out

        plan, setup, shapes = self.timed(device)
        wplan, warm, again = self.timed(device)
        check(not again, f"placement: warm repeat compiled {again}")
        check(max(len(r) for rr in tg.routes.values() for r in rr) > 2,
              "placement: topology has no multi-hop route")
        pol = HVLB_CC_B(alpha_max=3.0, alpha_step=0.05)   # plan_placement's
        t0 = time.perf_counter()
        ref = Scheduler(tg, policy=pol, backend="scalar")
        rplan = ref.submit(g)
        ref_s = time.perf_counter() - t0
        rsess, queue = session_queue(ref, pol)
        for p in (plan, wplan):
            self.tie.best(p.schedule.alpha, p.schedule.proc,
                          p.schedule.finish, rplan, rsess, queue,
                          "placement qwen3-8b")
        self.report("3 placement qwen3-8b 8 slices 2 pods", setup, warm,
                    ref_s, shapes, 2,
                    f" n={g.n} makespan_s={plan.makespan_s}"
                    f"@alpha={plan.schedule.alpha} reference="
                    f"{rplan.makespan}@alpha={rplan.best_alpha}")

    # -- 4 ------------------------------------------------------------
    def service(self) -> None:
        from repro.core import (HVLB_CC_B, Scheduler,
                                fully_switched_topology, random_spg)
        from repro.service import SchedulerService

        P = 16
        rng = np.random.default_rng(77)
        tg = fully_switched_topology(
            P, rates=rng.uniform(0.6, 1.2, size=P),
            link_speeds=rng.uniform(0.5, 3.0, size=P))
        pol = HVLB_CC_B(**P16_SWEEP)
        sizes = {"car-a": (100, 200, 300, 400), "car-b": (150, 250, 350, 500)}
        graphs = {t: [random_spg(n, np.random.default_rng(9000 + n),
                                 ccr=1.0, tg=tg, max_in=3, max_out=6)
                      for n in ns]
                  for t, ns in sizes.items()}
        drift = {t: (1, gs[1].n // 2, 1.3) for t, gs in graphs.items()}

        def device(suffix: str):
            svc = SchedulerService(tg, pol, workers=2, window=0.05,
                                   backend="pallas")
            try:
                return asyncio.run(_burst(svc, graphs, drift, suffix))
            finally:
                svc.close()

        got, setup, shapes = self.timed(lambda: device(""))
        wgot, warm, again = self.timed(lambda: device("-warm"))
        check(not again, f"service: warm repeat compiled {again}")
        t0 = time.perf_counter()
        refs = {}
        for t, gs in graphs.items():
            ref = Scheduler(tg, policy=pol, backend="scalar")
            fleet = ref.submit_many(gs)
            before = session_queue(ref, pol) + (fleet,)
            k, task, factor = drift[t]
            upd = ref.update(task_rates={fleet.offsets[k] + task: factor})
            refs[t] = (before, session_queue(ref, pol) + (upd,))
        ref_s = time.perf_counter() - t0
        n_plans = 0
        for responses, tag in ((got, ""), (wgot, "-warm")):
            for t, (before, after) in refs.items():
                for stage, (rsess, queue, rplan) in (("plan", before),
                                                     ("replan", after)):
                    views = responses[(t + tag, stage)]
                    dev_proc = np.concatenate([v["proc"] for v in views])
                    dev_fin = np.concatenate([v["finish"] for v in views])
                    self.tie.best(views[0]["alpha"], dev_proc, dev_fin,
                                  rplan, rsess, queue,
                                  f"service {t}{tag} {stage}")
                    n_plans += 1
        self.report("4 service TCP 2 tenants x 4 graphs", setup, warm,
                    ref_s, shapes, n_plans,
                    f" union_n={[sum(ns) for ns in sizes.values()]}")


async def _burst(svc, graphs, drift, suffix: str) -> dict:
    """One pipelined TCP burst: per tenant register every graph, plan
    each, drift one task's rate, plan each again.  Returns the per-graph
    plan views keyed by (tenant, "plan"|"replan")."""
    from repro.service.__main__ import serve
    from repro.service.protocol import (Request, decode_response,
                                        encode_request, spg_to_json)

    reqs: list = []
    stage_of: dict = {}

    def add(tenant, op, stage=None, **params):
        reqs.append(Request(len(reqs) + 1, op, tenant, params))
        stage_of[len(reqs)] = stage

    for t, gs in graphs.items():
        for k, g in enumerate(gs):
            add(t + suffix, "register", name=f"g{k}", graph=spg_to_json(g))
    for stage in ("plan", "update", "replan"):
        for t, gs in graphs.items():
            if stage == "update":
                k, task, factor = drift[t]
                add(t + suffix, "update", graph=f"g{k}",
                    task_rates={str(task): factor})
                continue
            for k in range(len(gs)):
                add(t + suffix, "plan", (t + suffix, stage), graph=f"g{k}")

    server = await serve(svc, "127.0.0.1", 0)
    try:
        host, port = server.sockets[0].getsockname()[:2]
        reader, writer = await asyncio.open_connection(host, port)
        for r in reqs:                                  # pipelined
            writer.write(encode_request(r))
        await writer.drain()
        got = {}
        while len(got) < len(reqs):
            resp = decode_response(
                await asyncio.wait_for(reader.readline(), timeout=900))
            got[resp.id] = resp
        writer.close()
        await writer.wait_closed()
    finally:
        server.close()
        await server.wait_closed()
    views: dict = {}
    for rid, resp in sorted(got.items()):
        req = reqs[rid - 1]
        check(resp.ok, f"service {req.tenant} {req.op}: {resp.error}")
        check(resp.result["backend"] == "pallas"
              and resp.result["fallback"] is None,
              f"service {req.tenant} {req.op}: backend "
              f"{resp.result['backend']} fallback {resp.result['fallback']}")
        if stage_of[rid] is not None:
            views.setdefault(stage_of[rid], []).append(resp.result)
    return views


# ------------------------------------------------------------------ main
def _count_files(path) -> int:
    """Entries in the compilation cache (0 = cold)."""
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(files) for _, _, files in os.walk(path))


def fail(msg: str) -> NoReturn:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def main() -> None:
    steering = [v for v in STEERING_ENV if v in os.environ]
    if steering:
        fail(f"unset {', '.join(steering)}: the smoke run takes the "
             f"default device path")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError:
        fail(f"the repro package is not next to this script ({ROOT}/src)")
    cache_dir = use_compile_cache()             # before the first jit
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"JAX found no device: {e}")
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX runs on {dev.platform}; this smoke run never "
             f"falls back to the CPU")
    # a backend demotion warns (RuntimeWarning): make it fail the run
    warnings.simplefilter("error", RuntimeWarning)
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"compile_cache={cache_dir} "
          f"cache_files_at_start={_count_files(cache_dir)}", flush=True)

    import repro.core.backends.pallas as pb

    smoke = Smoke(DeviceLog(pb), pb.F32_NEAR_TIE_RTOL)
    t0 = time.perf_counter()
    try:
        for phase in (smoke.paper, smoke.exp7_cell, smoke.placement,
                      smoke.service):
            phase()
        smoke.log.check_compiled()
    except SmokeFailure as e:
        fail(f"FAILED: {e}")
    print(f"total: wall_s={time.perf_counter() - t0:.3f} "
          f"plans={smoke.plans} scan_shapes={len(smoke.log.shapes)} "
          f"pallas_backends={len(smoke.log.backends)} "
          f"near_tie_flips={smoke.tie.flips} "
          f"cache_files_at_end={_count_files(cache_dir)}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
