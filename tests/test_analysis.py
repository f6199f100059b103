"""Fixture tests for the static invariant analyzer (repro.analysis).

Each rule gets a violating snippet that MUST produce a finding and a
clean snippet that must NOT (both run through the real CLI entry point
in explicit-path mode, where every rule applies), plus the baseline /
pragma mechanics and the self-check that the shipped repo analyzes
clean.  Everything here is pure-AST — no jax, no kernel execution.
"""
import io
import textwrap
from contextlib import redirect_stderr, redirect_stdout

import pytest

from repro.analysis import main

# ----------------------------------------------------------------------
# tiny harness: run the CLI on fixture sources, capture findings
# ----------------------------------------------------------------------


def run_cli(argv):
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        code = main(argv)
    return code, out.getvalue(), err.getvalue()


def analyze(tmp_path, source, rules=None, name="fixture.py"):
    path = tmp_path / name
    path.write_text(textwrap.dedent(source))
    argv = [str(path)]
    if rules:
        argv += ["--rules", rules]
    return run_cli(argv)


def assert_finds(tmp_path, source, rule):
    code, out, _ = analyze(tmp_path, source, rules=rule)
    assert code == 1, f"expected a {rule} finding, got exit {code}:\n{out}"
    assert f"[{rule}]" in out
    return out


def assert_clean(tmp_path, source, rule):
    code, out, _ = analyze(tmp_path, source, rules=rule)
    assert code == 0, f"expected clean under {rule}, got:\n{out}"


# ----------------------------------------------------------------------
# lint rules
# ----------------------------------------------------------------------


class TestFloatArith:
    def test_violation_literal(self, tmp_path):
        out = assert_finds(tmp_path, """
            def pick(best, s):
                if s.makespan < best.makespan - 1e-12:
                    return s
                return best
            """, "float-arith")
        assert ":3:" in out          # file:line location

    def test_violation_module_const(self, tmp_path):
        assert_finds(tmp_path, """
            MARGIN = 1e-6
            def skip(a, b):
                return a < b - MARGIN
            """, "float-arith")

    def test_clean_integer_and_comparison(self, tmp_path):
        assert_clean(tmp_path, """
            def pick(best, s, k):
                n = k + 1
                if s.makespan < best.makespan:
                    return s, n
                return best, n
            """, "float-arith")


class TestSentinelScope:
    def test_violation_reference(self, tmp_path):
        assert_finds(tmp_path, """
            from .faults import DOWN_COMP
            def mask(comp):
                comp[0] = DOWN_COMP
            """, "sentinel-scope")

    def test_violation_attribute(self, tmp_path):
        assert_finds(tmp_path, """
            from . import faults
            def check(eft):
                return eft < faults.INFEASIBLE_EFT
            """, "sentinel-scope")

    def test_clean(self, tmp_path):
        assert_clean(tmp_path, """
            def mask(comp, value):
                comp[0] = value
            """, "sentinel-scope")


class TestNondeterminism:
    def test_violation_wall_clock(self, tmp_path):
        assert_finds(tmp_path, """
            import time
            def stamp():
                return time.time()
            """, "nondeterminism")

    def test_violation_legacy_np_random(self, tmp_path):
        assert_finds(tmp_path, """
            import numpy as np
            def jitter(n):
                return np.random.rand(n)
            """, "nondeterminism")

    def test_clean_seeded_generator(self, tmp_path):
        assert_clean(tmp_path, """
            import time
            import numpy as np
            def jitter(n, seed):
                t0 = time.monotonic()
                rng = np.random.default_rng(seed)
                return rng.random(n), time.monotonic() - t0
            """, "nondeterminism")

    def test_violation_event_loop_clock(self, tmp_path):
        assert_finds(tmp_path, """
            import asyncio
            def now():
                loop = asyncio.get_running_loop()
                return loop.time()
            """, "nondeterminism")

    def test_event_loop_clock_allowed_behind_pragma(self, tmp_path):
        assert_clean(tmp_path, """
            import asyncio
            def now():
                # analysis: allow[nondeterminism] latency accounting only
                return asyncio.get_running_loop().time()
            """, "nondeterminism")


class TestSetIteration:
    def test_violation(self, tmp_path):
        assert_finds(tmp_path, """
            def procs(schedule):
                return [p for p in set(schedule.values())]
            """, "set-iteration")

    def test_clean_sorted(self, tmp_path):
        assert_clean(tmp_path, """
            def procs(schedule):
                return [p for p in sorted(set(schedule.values()))]
            """, "set-iteration")


class TestDeprecationRoute:
    def test_violation(self, tmp_path):
        assert_finds(tmp_path, """
            import warnings
            def old_entry():
                warnings.warn("use Scheduler", DeprecationWarning,
                              stacklevel=2)
            """, "deprecation-route")

    def test_clean_warn_once(self, tmp_path):
        assert_clean(tmp_path, """
            from .deprecation import warn_once
            def old_entry():
                warn_once("old_entry", "use Scheduler")
            """, "deprecation-route")


class TestHostSync:
    def test_violation(self, tmp_path):
        assert_finds(tmp_path, """
            def fetch(out):
                import jax
                return jax.device_get(out)
            """, "host-sync")

    def test_clean(self, tmp_path):
        assert_clean(tmp_path, """
            def fetch(out):
                return out
            """, "host-sync")


class TestUnusedImport:
    def test_violation(self, tmp_path):
        out = assert_finds(tmp_path, """
            import os
            import sys
            def main():
                return sys.argv
            """, "unused-import")
        assert "'os'" in out and "'sys'" not in out

    def test_clean_quoted_annotation_and_all(self, tmp_path):
        assert_clean(tmp_path, """
            from typing import TYPE_CHECKING
            from os import path
            if TYPE_CHECKING:
                from collections import OrderedDict
            __all__ = ["path", "use"]
            def use(d: "OrderedDict") -> "OrderedDict":
                return d
            """, "unused-import")


# ----------------------------------------------------------------------
# kernel rules
# ----------------------------------------------------------------------

# A miniature of the real backend idiom: helper lambdas build the
# BlockSpecs, carried out-blocks have a constant index map, the kernel
# resolves through functools.partial.
KERNEL_TEMPLATE = """\
import functools
import jax.experimental.pallas as pl

def _kernel(x_ref, y_ref, state_ref, *, K):
{body}

def build(B, K, shapes):
    full = lambda *s: pl.BlockSpec(s, lambda i: (0,) * len(s))
    dec = lambda *s: pl.BlockSpec((1,) + s, lambda i: (i,) + (0,) * len(s))
    in_specs = [dec(K)]
    out_specs = [dec(K), full(K)]
    kern = functools.partial(_kernel, K=K)
    return pl.pallas_call(kern, grid={grid}, in_specs=in_specs,
                          out_specs=out_specs, out_shape=shapes)
"""


def kernel_fixture(body, grid="(B,)"):
    indented = "\n".join("    " + ln if ln.strip() else ln
                         for ln in textwrap.dedent(body).strip().splitlines())
    return KERNEL_TEMPLATE.format(body=indented, grid=grid)


GOOD_BODY = """
    val = x_ref[0] + state_ref[0]
    y_ref[0] = val
    state_ref[0] = val
"""


class TestKernelCarried:
    def test_clean_single_commit(self, tmp_path):
        assert_clean(tmp_path, kernel_fixture(GOOD_BODY),
                     "kernel-carried-race,kernel-carried-uncommitted")

    def test_race_double_store(self, tmp_path):
        assert_finds(tmp_path, kernel_fixture("""
            val = x_ref[0] + state_ref[0]
            y_ref[0] = val
            state_ref[0] = val
            state_ref[1] = val
            """), "kernel-carried-race")

    def test_race_store_in_loop(self, tmp_path):
        assert_finds(tmp_path, kernel_fixture("""
            val = x_ref[0]
            y_ref[0] = val
            for h in range(4):
                state_ref[h] = val
            """), "kernel-carried-race")

    def test_exclusive_branches_are_one_commit(self, tmp_path):
        assert_clean(tmp_path, kernel_fixture("""
            val = x_ref[0]
            y_ref[0] = val
            if K > 1:
                state_ref[0] = val
            else:
                state_ref[0] = -val
            """), "kernel-carried-race,kernel-carried-uncommitted")

    def test_uncommitted(self, tmp_path):
        assert_finds(tmp_path, kernel_fixture("""
            y_ref[0] = x_ref[0] + state_ref[0]
            """), "kernel-carried-uncommitted")


class TestKernelGridCarry:
    def test_violation_2d_grid(self, tmp_path):
        # the 1-param `full` index map cannot even name the outer axis
        assert_finds(tmp_path, kernel_fixture(GOOD_BODY, grid="(B, K)"),
                     "kernel-grid-carry")

    def test_violation_2d_grid_leading_axis_ignored(self, tmp_path):
        # 2 params, but the leading (outer) axis is never used: every
        # outer index would revisit — and race on — the same block
        src = kernel_fixture(GOOD_BODY, grid="(B, K)").replace(
            "full = lambda *s: pl.BlockSpec(s, lambda i: (0,) * len(s))",
            "full = lambda *s: pl.BlockSpec(s, lambda a, i: (0,) * len(s))")
        assert_finds(tmp_path, src, "kernel-grid-carry")

    def test_clean_2d_grid_sweep_contract(self, tmp_path):
        # the (A, B) sweep shape: carry confined to the innermost axis,
        # the leading axis addresses an independent state copy per index
        src = kernel_fixture(GOOD_BODY, grid="(B, K)").replace(
            "full = lambda *s: pl.BlockSpec(s, lambda i: (0,) * len(s))",
            "full = lambda *s: pl.BlockSpec((1,) + s,"
            " lambda a, i: (a,) + (0,) * len(s))").replace(
            "dec = lambda *s: pl.BlockSpec((1,) + s, "
            "lambda i: (i,) + (0,) * len(s))",
            "dec = lambda *s: pl.BlockSpec((1, 1) + s, "
            "lambda a, i: (a, i) + (0,) * len(s))")
        assert_clean(tmp_path, src, "kernel-grid-carry")

    def test_clean_1d_grid(self, tmp_path):
        assert_clean(tmp_path, kernel_fixture(GOOD_BODY),
                     "kernel-grid-carry")


# A miniature of the whole-schedule scan idiom: the body function
# threads (lf, pf) through the carry and stacks per-step outputs.
SCAN_TEMPLATE = """\
import jax
import jax.numpy as jnp
from jax import lax

def step(carry, xs):
{body}

def schedule(lf0, pf0, waves):
    (lf, pf), ys = lax.scan(step, (lf0, pf0), waves)
    return lf, pf, ys
"""


def scan_fixture(body):
    indented = "\n".join("    " + ln if ln.strip() else ln
                         for ln in textwrap.dedent(body).strip().splitlines())
    return SCAN_TEMPLATE.format(body=indented)


SCAN_GOOD_BODY = """
    lf, pf = carry
    est = jnp.maximum(lf, xs)
    lf = lf + est
    pf = jnp.minimum(pf, est)
    return (lf, pf), est
"""


class TestScanCarry:
    def test_clean_one_bind_per_leaf(self, tmp_path):
        assert_clean(tmp_path, scan_fixture(SCAN_GOOD_BODY),
                     "scan-carry-race,scan-carry-uncommitted")

    def test_clean_exclusive_branches(self, tmp_path):
        assert_clean(tmp_path, scan_fixture("""
            lf, pf = carry
            est = jnp.maximum(lf, xs)
            if est.ndim:
                lf = lf + est
            else:
                lf = lf - est
            pf = jnp.minimum(pf, est)
            return (lf, pf), est
            """), "scan-carry-race,scan-carry-uncommitted")

    def test_clean_nested_function_scope_excluded(self, tmp_path):
        # a fori_loop body threads its own state tuple; its bindings
        # are not writes to the outer carry leaf
        assert_clean(tmp_path, scan_fixture("""
            lf, pf = carry
            def slot(b, st):
                lf, pf = st
                lf = lf + b
                return (lf, pf)
            lf, pf = lax.fori_loop(0, 4, slot, (lf, pf))
            return (lf, pf), lf
            """), "scan-carry-race,scan-carry-uncommitted")

    def test_race_double_bind(self, tmp_path):
        out = assert_finds(tmp_path, scan_fixture("""
            lf, pf = carry
            lf = lf + xs
            lf = lf * 2.0
            pf = jnp.minimum(pf, lf)
            return (lf, pf), lf
            """), "scan-carry-race")
        assert "2 bindings" in out

    def test_race_bind_in_loop(self, tmp_path):
        assert_finds(tmp_path, scan_fixture("""
            lf, pf = carry
            for h in range(4):
                lf = lf + xs
            pf = jnp.minimum(pf, lf)
            return (lf, pf), lf
            """), "scan-carry-race")

    def test_race_duplicate_carry_leaf(self, tmp_path):
        out = assert_finds(tmp_path, scan_fixture("""
            lf, pf = carry
            lf = lf + xs
            return (lf, lf), pf
            """), "scan-carry-race")
        assert "alias" in out

    def test_uncommitted_leaf(self, tmp_path):
        out = assert_finds(tmp_path, scan_fixture("""
            lf, pf = carry
            lf = lf + xs
            return (lf, pf), lf
            """), "scan-carry-uncommitted")
        assert "pf" in out

    def test_initial_unpack_not_counted_as_bind(self, tmp_path):
        # `lf, pf = carry` alone must read as ZERO commits, not one
        assert_finds(tmp_path, scan_fixture("""
            lf, pf = carry
            return (lf, pf), xs
            """), "scan-carry-uncommitted")


class TestKernelArity:
    def test_violation(self, tmp_path):
        # 3 kernel refs but 1+3 specs supplied
        src = kernel_fixture(GOOD_BODY).replace(
            "out_specs = [dec(K), full(K)]",
            "out_specs = [dec(K), dec(K), full(K)]")
        assert_finds(tmp_path, src, "kernel-arity")

    def test_clean(self, tmp_path):
        assert_clean(tmp_path, kernel_fixture(GOOD_BODY), "kernel-arity")


class TestKernelTilePad:
    def test_violation(self, tmp_path):
        assert_finds(tmp_path, """
            from .layout import pad_dim
            def dims(P, L):
                return pad_dim(P, 4), pad_dim(L, 128)
            """, "kernel-tile-pad")

    def test_clean(self, tmp_path):
        assert_clean(tmp_path, """
            from .layout import LANE, SUBLANE_F32, pad_dim
            def dims(P, L, tile):
                if tile:
                    return pad_dim(P, SUBLANE_F32), pad_dim(L, LANE)
                return pad_dim(P, 1), pad_dim(L, 1)
            """, "kernel-tile-pad")


class TestKernelDtype:
    def test_violation(self, tmp_path):
        assert_finds(tmp_path, kernel_fixture("""
            import jax.numpy as jnp
            val = x_ref[0].astype(jnp.float64)
            y_ref[0] = val
            state_ref[0] = val
            """), "kernel-dtype")

    def test_clean_ref_dtype(self, tmp_path):
        assert_clean(tmp_path, kernel_fixture("""
            f = x_ref.dtype
            val = x_ref[0].astype(f)
            y_ref[0] = val
            state_ref[0] = val
            """), "kernel-dtype")


class TestKernelRtolSite:
    def test_violation(self, tmp_path):
        assert_finds(tmp_path, """
            F32_NEAR_TIE_RTOL = 1e-5
            def near(a, b):
                return abs(a - b) <= F32_NEAR_TIE_RTOL * abs(b)
            """, "kernel-rtol-site")

    def test_clean_definition_only(self, tmp_path):
        assert_clean(tmp_path, """
            F32_NEAR_TIE_RTOL = 1e-5
            """, "kernel-rtol-site")


# ----------------------------------------------------------------------
# typing gate rules
# ----------------------------------------------------------------------

PROTOCOL = """
    import abc

    class CandidateEvaluator(abc.ABC):
        name = "base"

        @abc.abstractmethod
        def _alloc(self):
            ...

        @abc.abstractmethod
        def evaluate(self, j):
            ...

        def evaluate_batch(self, js):
            return [self.evaluate(j) for j in js]
"""


class TestTypingGate:
    def test_protocol_missing(self, tmp_path):
        assert_finds(tmp_path, PROTOCOL + """
            class HalfBackend(CandidateEvaluator):
                name = "half"
                def _alloc(self):
                    ...
            """, "protocol-missing")

    def test_protocol_signature(self, tmp_path):
        out = assert_finds(tmp_path, PROTOCOL + """
            class RenamedBackend(CandidateEvaluator):
                name = "renamed"
                def _alloc(self):
                    ...
                def evaluate(self, task):
                    ...
            """, "protocol-signature")
        assert "evaluate" in out

    def test_protocol_extra_arg_without_default(self, tmp_path):
        assert_finds(tmp_path, PROTOCOL + """
            class GreedyBackend(CandidateEvaluator):
                name = "greedy"
                def _alloc(self):
                    ...
                def evaluate(self, j, extra):
                    ...
            """, "protocol-signature")

    def test_backend_name(self, tmp_path):
        assert_finds(tmp_path, PROTOCOL + """
            class AnonBackend(CandidateEvaluator):
                def _alloc(self):
                    ...
                def evaluate(self, j):
                    ...
            """, "backend-name")

    def test_clean_backend(self, tmp_path):
        assert_clean(tmp_path, PROTOCOL + """
            class GoodBackend(CandidateEvaluator):
                name = "good"
                def _alloc(self):
                    ...
                def evaluate(self, j):
                    ...
                def evaluate_batch(self, js, chunk=4):
                    return super().evaluate_batch(js)
            """, "protocol-missing,protocol-signature,backend-name")


# ----------------------------------------------------------------------
# concurrency rules (service-layer race detector)
# ----------------------------------------------------------------------

# the hybrid idiom under test: an async front door, a per-lane thread
# executor, a threading.Lock around shared state
SVC_HEADER = """
            import asyncio
            import threading
            import time
            from concurrent.futures import ThreadPoolExecutor
"""


class TestRaceUnguardedShared:
    def test_violation_loop_writes_worker_reads(self, tmp_path):
        out = assert_finds(tmp_path, SVC_HEADER + """
            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._ex = ThreadPoolExecutor(1)
                    self._stats = {}

                async def request(self, key):
                    self._stats[key] = 1
                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(self._ex, self._work, key)

                def _work(self, key):
                    with self._lock:
                        self._stats[key] += 1

                def close(self):
                    self._ex.shutdown(wait=True)
            """, "race-unguarded-shared")
        assert "self._stats" in out and "self._lock" in out

    def test_violation_no_lock_anywhere(self, tmp_path):
        out = assert_finds(tmp_path, SVC_HEADER + """
            class Svc:
                def __init__(self):
                    self._ex = ThreadPoolExecutor(1)
                    self._seen = set()

                async def request(self, key):
                    if key in self._seen:
                        return
                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(self._ex, self._work, key)

                def _work(self, key):
                    self._seen.add(key)

                def close(self):
                    self._ex.shutdown(wait=True)
            """, "race-unguarded-shared")
        assert "no access holds a lock" in out

    def test_clean_every_site_guarded(self, tmp_path):
        assert_clean(tmp_path, SVC_HEADER + """
            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._ex = ThreadPoolExecutor(1)
                    self._stats = {}
                    self.batch = 8        # immutable config: not flagged

                async def request(self, key):
                    with self._lock:
                        self._stats[key] = self.batch
                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(self._ex, self._work, key)

                def _work(self, key):
                    with self._lock:
                        self._stats[key] += self.batch

                def close(self):
                    self._ex.shutdown(wait=True)
            """, "race-unguarded-shared")


class TestAwaitUnderLock:
    def test_violation_await(self, tmp_path):
        assert_finds(tmp_path, SVC_HEADER + """
            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()

                async def tick(self):
                    with self._lock:
                        await asyncio.sleep(0.1)
            """, "race-await-under-lock")

    def test_violation_lane_lock_acquisition(self, tmp_path):
        assert_finds(tmp_path, SVC_HEADER + """
            class Svc:
                def __init__(self, workers):
                    self._lock = threading.Lock()
                    self._locks = [asyncio.Lock() for _ in range(workers)]

                async def flush(self, lane):
                    with self._lock:
                        async with self._locks[lane]:
                            pass
            """, "race-await-under-lock")

    def test_clean_await_outside_and_alias_resolution(self, tmp_path):
        assert_clean(tmp_path, SVC_HEADER + """
            class Svc:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.n = 0

                async def tick(self):
                    lock = self._lock
                    with lock:
                        self.n += 1
                    await asyncio.sleep(0.1)
            """, "race-await-under-lock")


class TestLoopBlockingCall:
    def test_violation_time_sleep(self, tmp_path):
        assert_finds(tmp_path, SVC_HEADER + """
            async def backoff():
                time.sleep(0.5)
            """, "loop-blocking-call")

    def test_violation_direct_scheduler_call(self, tmp_path):
        out = assert_finds(tmp_path, SVC_HEADER + """
            class Svc:
                def __init__(self, sched):
                    self.sched = sched

                async def replan(self, graph):
                    self.sched.submit(graph)
            """, "loop-blocking-call")
        assert "Scheduler.submit" in out

    def test_violation_future_result(self, tmp_path):
        assert_finds(tmp_path, SVC_HEADER + """
            async def wait_for(fut):
                return fut.result()
            """, "loop-blocking-call")

    def test_clean_worker_side_and_executor_routing(self, tmp_path):
        assert_clean(tmp_path, SVC_HEADER + """
            class Svc:
                def __init__(self, sched):
                    self.sched = sched
                    self._ex = ThreadPoolExecutor(1)

                async def replan(self, graph):
                    await asyncio.sleep(0.01)
                    loop = asyncio.get_running_loop()
                    return await loop.run_in_executor(
                        self._ex, self._run, graph)

                def _run(self, graph):
                    time.sleep(0.001)     # blocking is fine on a worker
                    return self.sched.submit(graph)

                def close(self):
                    self._ex.shutdown(wait=True)
            """, "loop-blocking-call")


class TestCrossThreadFuture:
    def test_violation_set_result_from_worker(self, tmp_path):
        assert_finds(tmp_path, SVC_HEADER + """
            def _resolve(fut, value):
                fut.set_result(value)

            class Svc:
                def __init__(self):
                    self._ex = ThreadPoolExecutor(1)

                async def run(self, fut):
                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(self._ex, _resolve, fut, 1)

                def close(self):
                    self._ex.shutdown(wait=True)
            """, "race-cross-thread-future")

    def test_clean_call_soon_threadsafe_discipline(self, tmp_path):
        assert_clean(tmp_path, SVC_HEADER + """
            def _set_result(fut, value):
                if not fut.done():
                    fut.set_result(value)

            def _resolve(fut, value):
                fut.get_loop().call_soon_threadsafe(_set_result, fut, value)

            class Svc:
                def __init__(self):
                    self._ex = ThreadPoolExecutor(1)

                async def run(self, fut):
                    loop = asyncio.get_running_loop()
                    await loop.run_in_executor(self._ex, _resolve, fut, 1)

                def close(self):
                    self._ex.shutdown(wait=True)
            """, "race-cross-thread-future")


class TestLeakExecutor:
    def test_violation_attribute_never_joined(self, tmp_path):
        assert_finds(tmp_path, SVC_HEADER + """
            class Svc:
                def __init__(self):
                    self._ex = ThreadPoolExecutor(4)

                async def run(self, fn):
                    loop = asyncio.get_running_loop()
                    return await loop.run_in_executor(self._ex, fn)
            """, "leak-executor")

    def test_violation_local_never_shut_down(self, tmp_path):
        assert_finds(tmp_path, SVC_HEADER + """
            def fan_out(jobs):
                ex = ThreadPoolExecutor(2)
                for j in jobs:
                    ex.submit(j)
            """, "leak-executor")

    def test_clean_joined_in_close_and_scoped_local(self, tmp_path):
        assert_clean(tmp_path, SVC_HEADER + """
            class Svc:
                def __init__(self):
                    self._ex = ThreadPoolExecutor(4)

                async def run(self, fn):
                    loop = asyncio.get_running_loop()
                    return await loop.run_in_executor(self._ex, fn)

                def close(self):
                    self._ex.shutdown(wait=True)

            def fan_out(jobs):
                with ThreadPoolExecutor(2) as ex:
                    return [ex.submit(j) for j in jobs]
            """, "leak-executor")


class TestGcTaskRef:
    def test_violation_fire_and_forget(self, tmp_path):
        assert_finds(tmp_path, SVC_HEADER + """
            async def arm(coro):
                asyncio.create_task(coro)
            """, "gc-task-ref")

    def test_violation_assigned_but_unanchored(self, tmp_path):
        assert_finds(tmp_path, SVC_HEADER + """
            async def arm(coro):
                task = asyncio.ensure_future(coro)
                print("armed", task is not None)
            """, "gc-task-ref")

    def test_clean_anchored_in_container(self, tmp_path):
        assert_clean(tmp_path, SVC_HEADER + """
            class Svc:
                def __init__(self):
                    self._tasks = set()

                async def arm(self, coro):
                    task = asyncio.get_running_loop().create_task(coro)
                    self._tasks.add(task)
                    task.add_done_callback(self._tasks.discard)
            """, "gc-task-ref")

    def test_clean_awaited(self, tmp_path):
        assert_clean(tmp_path, SVC_HEADER + """
            async def arm(coro):
                task = asyncio.ensure_future(coro)
                return await task
            """, "gc-task-ref")


# ----------------------------------------------------------------------
# suppression pragma + ratchet baseline mechanics
# ----------------------------------------------------------------------


class TestPragma:
    def test_justified_pragma_suppresses(self, tmp_path):
        assert_clean(tmp_path, """
            def pick(best, s):
                # analysis: allow[float-arith] comparison epsilon, not a decision value
                if s.makespan < best.makespan - 1e-12:
                    return s
                return best
            """, "float-arith")

    def test_pragma_without_reason_is_a_finding(self, tmp_path):
        code, out, _ = analyze(tmp_path, """
            def pick(best, s):
                # analysis: allow[float-arith]
                if s.makespan < best.makespan - 1e-12:
                    return s
                return best
            """)
        assert code == 1
        assert "[allow-without-reason]" in out

    def test_pragma_is_rule_specific(self, tmp_path):
        assert_finds(tmp_path, """
            def pick(best, s):
                # analysis: allow[host-sync] wrong rule id
                if s.makespan < best.makespan - 1e-12:
                    return s
                return best
            """, "float-arith")


class TestBaseline:
    SRC = """
        def pick(best, s):
            if s.makespan < best.makespan - 1e-12:
                return s
            return best
        """

    def test_baselined_finding_passes_and_stale_fails(self, tmp_path):
        path = tmp_path / "fixture.py"
        path.write_text(textwrap.dedent(self.SRC))
        baseline = tmp_path / "baseline.txt"

        code, _, _ = run_cli([str(path), "--rules", "float-arith",
                              "--baseline", str(baseline),
                              "--write-baseline"])
        assert code == 0
        assert "float-arith" in baseline.read_text()

        code, out, _ = run_cli([str(path), "--rules", "float-arith",
                                "--baseline", str(baseline)])
        assert code == 0, out          # tolerated by the ratchet

        # fix the code: the baseline entry goes stale and must be removed
        path.write_text(textwrap.dedent("""
            def pick(best, s):
                if s.makespan < best.makespan:
                    return s
                return best
            """))
        code, out, _ = run_cli([str(path), "--rules", "float-arith",
                                "--baseline", str(baseline)])
        assert code == 1
        assert "stale baseline entry" in out

    def test_missing_baseline_file_is_config_error(self, tmp_path):
        path = tmp_path / "fixture.py"
        path.write_text("x = 1\n")
        code, _, err = run_cli([str(path),
                                "--baseline", str(tmp_path / "nope.txt")])
        assert code == 2
        assert "does not exist" in err


# ----------------------------------------------------------------------
# CLI plumbing + repo self-check
# ----------------------------------------------------------------------


class TestCli:
    def test_unknown_rule_is_config_error(self, tmp_path):
        path = tmp_path / "fixture.py"
        path.write_text("x = 1\n")
        code, _, err = run_cli([str(path), "--rules", "no-such-rule"])
        assert code == 2
        assert "no-such-rule" in err

    def test_syntax_error_is_config_error(self, tmp_path):
        path = tmp_path / "fixture.py"
        path.write_text("def broken(:\n")
        code, _, err = run_cli([str(path)])
        assert code == 2
        assert "syntax error" in err

    def test_list_rules_covers_all_passes(self):
        code, out, _ = run_cli(["--list-rules"])
        assert code == 0
        rules = set(out.split())
        for rule in ("kernel-carried-race", "kernel-tile-pad",
                     "kernel-dtype", "float-arith", "sentinel-scope",
                     "nondeterminism", "host-sync", "unused-import",
                     "protocol-missing", "protocol-signature",
                     "race-unguarded-shared", "race-await-under-lock",
                     "loop-blocking-call", "race-cross-thread-future",
                     "leak-executor", "gc-task-ref"):
            assert rule in rules

    def test_findings_carry_file_line_locations(self, tmp_path):
        path = tmp_path / "fixture.py"
        path.write_text("import os\nx = 1\n")
        code, out, _ = run_cli([str(path), "--rules", "unused-import"])
        assert code == 1
        assert f"{path}:1: [unused-import]" in out

    def test_directory_arguments_expand_sorted_and_deduped(self, tmp_path):
        (tmp_path / "b.py").write_text("import os\nx = 1\n")
        (tmp_path / "a.py").write_text("y = 2\n")
        # the directory overlaps the explicit file: analyzed once
        code, out, _ = run_cli([str(tmp_path), str(tmp_path / "b.py"),
                                "--rules", "unused-import"])
        assert code == 1
        assert out.count("[unused-import]") == 1
        assert "across 2 file(s)" in out

    def test_missing_path_is_config_error(self, tmp_path):
        code, _, err = run_cli([str(tmp_path / "nope.py")])
        assert code == 2
        assert "no such file or directory" in err

    def test_repo_mode_paths_filter(self):
        code, out, _ = run_cli(["--paths", "src/repro/service/"])
        assert code == 0, out
        assert "clean" in out

    def test_paths_filter_without_match_is_config_error(self):
        code, _, err = run_cli(["--paths", "src/repro/nope/"])
        assert code == 2
        assert "matches no repo files" in err

    def test_paths_filter_rejected_in_explicit_mode(self, tmp_path):
        path = tmp_path / "fixture.py"
        path.write_text("x = 1\n")
        code, _, err = run_cli([str(path), "--paths", "src/repro/"])
        assert code == 2
        assert "repo-mode" in err


class TestJsonFormat:
    def test_one_object_per_line_with_schema(self, tmp_path):
        import json
        path = tmp_path / "fixture.py"
        path.write_text("import os\nx = 1\n")
        code, out, _ = run_cli([str(path), "--rules", "unused-import",
                                "--format", "json"])
        assert code == 1
        objs = [json.loads(line) for line in out.splitlines()]
        assert len(objs) == 1
        (f,) = objs
        assert list(f) == ["rule", "path", "line", "source",
                           "fingerprint", "message"]
        assert f["rule"] == "unused-import"
        assert f["path"] == str(path)
        assert f["line"] == 1
        assert f["source"] == "import os"
        assert f["fingerprint"] == f"{path}::unused-import::import os"
        assert "'os'" in f["message"]

    def test_clean_json_run_prints_nothing(self, tmp_path):
        path = tmp_path / "fixture.py"
        path.write_text("x = 1\n")
        code, out, _ = run_cli([str(path), "--format", "json"])
        assert code == 0
        assert out == ""

    def test_stale_baseline_entry_as_object(self, tmp_path):
        import json
        path = tmp_path / "fixture.py"
        path.write_text("import os\nx = 1\n")
        baseline = tmp_path / "baseline.txt"
        code, _, _ = run_cli([str(path), "--rules", "unused-import",
                              "--baseline", str(baseline),
                              "--write-baseline"])
        assert code == 0
        path.write_text("x = 1\n")       # fix it: entry goes stale
        code, out, _ = run_cli([str(path), "--rules", "unused-import",
                                "--baseline", str(baseline),
                                "--format", "json"])
        assert code == 1
        (obj,) = [json.loads(line) for line in out.splitlines()]
        assert obj["rule"] == "stale-baseline-entry"
        assert obj["fingerprint"].endswith("::unused-import::import os")


class TestProjectIndex:
    def test_repeated_load_parses_once(self, tmp_path):
        from repro.analysis.index import ProjectIndex
        path = tmp_path / "mod.py"
        path.write_text("x = 1\n")
        index = ProjectIndex()
        sf1 = index.load(path, "mod.py")
        sf2 = index.load(path, "mod.py")
        assert sf1 is sf2
        assert index.parse_count == 1

    def test_all_passes_share_one_parse_per_file(self, tmp_path,
                                                 monkeypatch):
        """The refactor's point: a full CLI run (all four passes) parses
        each file exactly once."""
        import ast as ast_module
        from repro.analysis import index as index_module
        counts = {}
        real_parse = ast_module.parse

        def counting_parse(source, filename="<unknown>", *a, **kw):
            counts[filename] = counts.get(filename, 0) + 1
            return real_parse(source, filename, *a, **kw)

        monkeypatch.setattr(index_module.ast, "parse", counting_parse)
        paths = []
        for name in ("one.py", "two.py", "three.py"):
            p = tmp_path / name
            p.write_text("import os\nx = 1\n")
            paths.append(str(p))
        code, _, _ = run_cli(paths)
        assert code == 1                  # unused-import fires
        assert counts == {p: 1 for p in paths}

    def test_syntax_error_recorded_not_retried(self, tmp_path):
        from repro.analysis.index import ProjectIndex
        path = tmp_path / "bad.py"
        path.write_text("def broken(:\n")
        index = ProjectIndex()
        assert index.load(path, "bad.py") is None
        assert index.load(path, "bad.py") is None
        assert len(index.errors) == 1
        assert index.parse_count == 0


def test_shipped_repo_analyzes_clean():
    """The acceptance gate: repo mode (scoped rules + committed ratchet
    baseline) over the shipped tree exits 0."""
    code, out, _ = run_cli([])
    assert code == 0, f"shipped tree has analyzer findings:\n{out}"
    assert "clean" in out


def test_shipped_scan_carry_is_checked():
    """The scan-carry rules reach the shipped whole-schedule scan: the
    analyzer resolves its body, reads all seven carried leaves and finds
    them sound.  A wrapper around the body would hide the carry and turn
    both rules off without a finding."""
    import ast
    from pathlib import Path

    import repro.analysis
    from repro.analysis import kernels
    from repro.analysis.index import ProjectIndex

    path = (Path(repro.analysis.__file__).parents[1] / "core" / "backends"
            / "pallas.py")
    sf = ProjectIndex().load(path, "pallas.py")
    calls = [node for node in ast.walk(sf.functions["_scan_run"])
             if isinstance(node, ast.Call) and kernels._is_scan_call(node)]
    assert len(calls) == 1
    body = sf.functions[calls[0].args[0].id]
    assert kernels._carry_leaves(body) == [
        "lf", "pf", "loads", "lop", "bp", "aft_t", "proc_t"]
    assert kernels._check_scan("pallas.py", calls[0], sf.functions) == []
