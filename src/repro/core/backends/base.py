"""Candidate-evaluation backend protocol (the numeric layer of the engine).

:class:`~repro.core.engine.CompiledInstance` is split in two:

  * the **decision layer** (``engine._run``) owns the priority-queue walk,
    precedence checks, decision-trace recording/resume, and `Schedule`
    assembly — pure Python, identical for every backend;
  * the **numeric layer** (a :class:`CandidateEvaluator`) owns the
    per-task candidate evaluation over all ``P`` processors — the
    sequential message-routing walks (Eqs. 13-15), the batched EST/EFT
    (Eqs. 10-12), the BP load-balance term (Def. 4.1), the selection
    value (Def. 4.2), winner selection, and the alpha crossing bound.

A backend owns the mutable run state: ``link_free`` (flat, link-id
indexed — a Python list for the scalar backend, a ``(L,)`` ndarray for
the vector backend), ``proc_free``, ``loads``, and the per-task
``proc_of``/``ast``/``aft`` outputs.  Committing a decision
(:meth:`apply`) is *shared* scalar code: a handful of per-hop max
updates, identical floats in identical order no matter which backend
produced the decision.  That is what makes decision traces portable — a
trace recorded under one backend replays bit-identically under another.

Invariant: every backend performs the same IEEE-754 operations as the
reference ``list_schedule`` (reassociating only *exact* operations such
as ``max``), so all backends are mutually **bit-identical**
(``tests/test_backend_equivalence.py``).
"""
from __future__ import annotations

import abc
import time
from typing import (Callable, ClassVar, List, NamedTuple, Optional, Sequence,
                    Tuple, TYPE_CHECKING)

import numpy as np

from ..faults import WaveTimeoutError

if TYPE_CHECKING:                                   # pragma: no cover
    from ..engine import CompiledInstance

__all__ = ["BackendCompatError", "CandidateEvaluator", "Decision",
           "PlanSweep"]

_INF = float("inf")


class BackendCompatError(ValueError):
    """The instance's topology cannot be expressed by this backend.

    Raised eagerly by :func:`~..backends.resolve_backend_name` when an
    explicit backend request is incompatible with the topology (so no
    session/plan cache is ever keyed for a plan that cannot be built),
    and defensively by backend constructors.
    """


# What `evaluate` returns: the DecisionRecord tail plus the decision's
# alpha crossing-bound contribution (inf when not tracking):
#   (proc, est, eft, msgs, cand_A, cand_B, bound_contrib)
# with ``msgs`` = [(pred, route, [(link_id, lst, lft), ...]), ...].
Decision = Tuple[int, float, float, list, Optional[tuple], Optional[tuple],
                 float]


class PlanSweep(NamedTuple):
    """What :meth:`CandidateEvaluator.evaluate_plan_sweep` returns.

    ``eft[a, k]`` is the winner's EFT of the ``k``-th decision of the wave
    plan (the waves flattened, i.e. queue order) under the ``a``-th alpha,
    in the backend's dtype: enough to price and select every alpha at
    once.  ``decode(a)`` gives alpha ``a``'s ``[wave] -> decisions``,
    built only when asked for; it commits nothing, so it may run any time
    later, from any thread, and gives the same decisions each time.
    """

    eft: np.ndarray
    decode: Callable[[int], List[List[Decision]]]


class CandidateEvaluator(abc.ABC):
    """One candidate-evaluation backend bound to one compiled instance.

    Lifecycle per ``_run``: ``start(alpha, period, want_bound)`` resets
    the run state, then for every dequeued task either
    ``evaluate(j)`` + ``apply(rec)`` (full candidate loop) or
    ``apply(rec)`` alone (trace replay of a memoized decision).
    """

    name: ClassVar[str]

    def __init__(self, inst: "CompiledInstance") -> None:
        self.inst = inst

    # -------------------------------------------------------------- run
    def start(self, alpha: float, period: float, want_bound: bool) -> None:
        inst = self.inst
        self.alpha = alpha
        self.period = period
        self.want_bound = want_bound
        self.proc_of: List[int] = [-1] * inst.n
        self.ast: List[float] = [0.0] * inst.n
        self.aft: List[float] = [0.0] * inst.n
        self._alloc()

    @abc.abstractmethod
    def _alloc(self) -> None:
        """Allocate/reset ``link_free``, ``proc_free``, ``loads`` in the
        backend's preferred container (list vs ndarray)."""

    @abc.abstractmethod
    def evaluate(self, j: int) -> Decision:
        """Evaluate all P placement candidates for task ``j`` against the
        current run state and pick the winner (Eqs. 10-15, Defs. 4.1-4.2).
        Does NOT mutate run state — the caller commits via :meth:`apply`.
        """

    def evaluate_batch(self, js: Sequence[int]) -> List[Decision]:
        """Evaluate-and-commit a batch of *independent* tasks, in order.

        The engine's decision layer groups consecutive same-rank-level
        queue entries (no precedence edges inside a batch — every
        predecessor is already committed) and hands the whole wave to the
        backend.  Decisions inside a batch still interact through the
        shared link/processor state, so they are evaluated and committed
        **sequentially**; batching changes where the loop runs, never the
        decisions.

        This default runs the per-decision path verbatim — ``evaluate``
        then :meth:`apply` per task, the exact op order of the unbatched
        engine — so the scalar/vector backends stay bit-exact and their
        traces trace-portable by construction.  A device backend
        overrides this to evaluate the whole batch in one kernel launch
        with in-kernel commits (see ``backends/pallas.py``), returning
        the same per-task :data:`Decision` tuples.

        Contract: run state after ``evaluate_batch(js)`` equals the
        state after ``for j in js: apply(j, *evaluate(j)[:3], ...)`` up
        to the backend's precision contract, and the returned decisions
        are in ``js`` order.
        """
        decisions: List[Decision] = []
        for j in js:
            d = self.evaluate(j)
            self.apply(j, d[0], d[1], d[2], d[3])
            decisions.append(d)
        return decisions

    def evaluate_plan(self, waves: Sequence[Sequence[int]],
                      timeout: Optional[float] = None,
                      bid0: int = 0) -> List[List[Decision]]:
        """Evaluate-and-commit a whole **wave plan** (the full schedule).

        The engine's decision layer now emits the complete level-batched
        wave plan up front (:func:`~..engine.plan_waves` — a pure
        function of the queue and the precedence edges) and hands it to
        the backend in one call.  This sequential default walks the plan
        wave by wave through :meth:`evaluate_batch` — the exact op order
        of the interleaved engine loop it replaced, so the scalar/vector
        backends stay bit-exact by construction.  A device backend
        overrides this to run the *entire* plan in a single dispatch
        (the Pallas ``lax.scan`` path) and decode one fetch.

        ``timeout`` is the engine's per-wave watchdog budget: the
        default raises :class:`~..faults.WaveTimeoutError` when one
        ``evaluate_batch`` overruns it (``bid0 + k`` names the offending
        wave's batch id); a whole-plan backend compares its single
        dispatch against ``timeout * len(waves)``.

        Contract: returns one decision list per wave, ``waves[k]``
        order; run state afterwards equals the sequential walk's.
        """
        out: List[List[Decision]] = []
        for k, wave in enumerate(waves):
            if timeout is None:
                out.append(self.evaluate_batch(wave))
            else:
                t0 = time.monotonic()
                out.append(self.evaluate_batch(wave))
                elapsed = time.monotonic() - t0
                if elapsed > timeout:
                    raise WaveTimeoutError(bid0 + k, elapsed, timeout)
        return out

    # ------------------------------------------------------- fused sweep
    def supports_plan_sweep(self) -> bool:
        """Whether :meth:`evaluate_plan_sweep` evaluates a whole alpha
        grid in one dispatch.  Default: no — the session API keeps the
        (trace-invariance-pruned) host-side per-alpha loop."""
        return False

    def evaluate_plan_sweep(self, waves: Sequence[Sequence[int]],
                            alphas: Sequence[float], period: float,
                            timeout: Optional[float] = None) -> PlanSweep:
        """Evaluate one wave plan under *every* alpha of a sweep grid in
        a single dispatch (the (A, B) fused launch, DESIGN.md §5).

        Returns a :class:`PlanSweep`: every alpha's winner EFTs at once,
        and each alpha's decisions on request, identical to
        ``len(alphas)`` independent :meth:`evaluate_plan` runs and
        decoded with bound tracking (``cand_A``/``cand_B`` populated) so
        the recorded traces resume exactly like host-loop sweep traces.
        A caller that reads one alpha pays the decode of one.  Must NOT
        commit to the backend's run state — the per-alpha runs are
        independent, and an alpha decoded later must not read run state
        another plan has committed since; callers re-``start()`` before
        reusing the instance.  Only called when :meth:`supports_plan_sweep` is true.
        """
        raise NotImplementedError(
            f"backend {self.name!r} does not fuse alpha sweeps")

    # ------------------------------------------------------------ commit
    def apply(self, j: int, p: int, est: float, eft: float,
              msgs: list) -> None:
        """Commit one decision (fresh or replayed from a trace).

        Scalar on purpose: a committed decision touches only the winner's
        row — a few floats — and sharing this code across backends is
        what guarantees a trace replays bit-identically anywhere.
        """
        self.proc_of[j] = p
        self.ast[j] = est
        self.aft[j] = eft
        self.proc_free[p] = eft
        self.loads[p] += self.inst._comp[j][p]
        link_free = self.link_free
        for (_i, _route, iv) in msgs:
            for (lid, _s, f) in iv:
                if f > link_free[lid]:
                    link_free[lid] = f

    # ------------------------------------------------------------- bound
    @staticmethod
    def crossing(p: int, cand_A: Sequence[float], cand_B: Sequence[float],
                 alpha: float) -> float:
        """Supremum-alpha contribution of one decision (see DESIGN §3).

        For winner ``p`` with per-candidate linear selection values
        ``A_r + B_r * a``, returns the smallest rival crossing point
        ``(A_r - A_p) / (B_p - B_r)`` — or ``alpha`` itself when a rival
        is numerically indistinguishable — or ``inf`` when the winner
        keeps winning forever.  Shared reference implementation used for
        trace replay; backends may vectorize the live path as long as
        they produce the identical float.
        """
        bound = _INF
        a_c, b_c = cand_A[p], cand_B[p]
        n = len(cand_A)
        for r in range(n):
            if r == p:
                continue
            d_b = b_c - cand_B[r]
            d_a = cand_A[r] - a_c
            scale = abs(a_c) + abs(cand_A[r]) + 1.0
            if d_b > 1e-15 * scale:
                a_star = d_a / d_b
                if a_star < bound:
                    bound = a_star
            elif abs(d_b) <= 1e-15 * scale and abs(d_a) <= 1e-12 * scale:
                if alpha < bound:
                    bound = alpha
        return bound
