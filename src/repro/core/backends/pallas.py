"""JAX/Pallas candidate-evaluation backend: one device kernel per *batch*.

The engine's decision layer hands this backend whole **waves** of
independent, same-rank-level tasks (``evaluate_batch``); a single
:func:`pallas_call` evaluates every decision of the wave over all ``P``
placement candidates, commits each winner to device-resident link and
processor state *inside the kernel*, and returns the per-decision
winner/EFT/coefficient arrays in one host transfer.  Host round-trips
per schedule therefore drop from O(decisions) (the PR-4 per-decision
kernel) to O(levels) — the HVLB_CC (B) priority order is approximately
level-sorted, so the queue decomposes into roughly one wave per rank
level.

Per batch the kernel unrolls the decisions in queue order; decision
``b``:

  1. broadcasts the carried ``(L,)`` link state into a ``(P, L)`` *lane
     buffer* (lane ``p`` = candidate processor ``p``'s tentative link
     state),
  2. walks the task's predecessors in the scalar reference's
     ``(aft, id)`` order; per predecessor it runs the Eq. 13-14
     recurrences as **masked row ops** — ``avail_h`` is a masked max
     over the link axis, ``LST``/``LFT`` are running ``(P,)`` maxima —
     selects the best route per lane by the lexicographic
     ``(LFT, hops, index)`` rule, and commits the winning route's hop
     LFTs back into the lane buffer (masked writes),
  3. batches Eqs. 10-12 and Defs. 4.1-4.2 over all lanes, picks the
     strict lexicographic ``(value, EFT, proc)`` argmin winner, and
  4. **commits in-kernel**: the winner lane's column of the lane buffer
     *is* the post-decision link state (masked overwrites reproduce the
     scalar max-commits exactly), and ``proc_free``/``loads``/
     ``loads/period``/``BP`` update through a winner one-hot — so
     decision ``b+1`` evaluates against exactly the state the scalar
     walk would have left.

Link/processor state lives on device across the whole schedule: the
kernel returns the updated state arrays, which stay on device as the
carry for the next wave (never fetched).  The host keeps float64
mirrors in sync through the *shared* scalar
:meth:`~.base.CandidateEvaluator.apply` commits on the returned
decision floats — that is what keeps decision traces backend-portable
(pallas <-> scalar resume) — and re-uploads the mirrors wholesale
(one transfer, ``_state_dirty``) after a trace replay touched them.

Precision has two modes, selected per process:

  * **float64 interpreter** (the default off-TPU, CI): every operation
    is the same IEEE-754 double arithmetic as the scalar reference — in
    practice bit-identical, asserted decision-identical
    (``tests/test_backend_equivalence.py``).
  * **float32 tiled** (the default on TPU, where f64 does not exist;
    forced anywhere via ``REPRO_PALLAS_DTYPE=float32`` for testing):
    shapes are tile-padded (``layout.pad_dim`` — P to a sublane
    multiple, L to a lane multiple) so the kernel Mosaic-compiles, and
    the contract relaxes to the documented **near-tie policy**: the
    schedule is decision-identical to scalar except where two
    candidates' selection values differ by less than
    :data:`F32_NEAR_TIE_RTOL` (relative), in which case the winner is
    the f32-lexicographic ``(value, EFT, proc)`` argmin — pinned
    deterministic for fixed inputs (first index on exact f32 ties).
    ``REPRO_PALLAS_TILE=1/0`` forces tile padding independently (the
    padding is arithmetic-neutral, so it can be exercised under the
    interpreter).

``REPRO_PALLAS_INTERPRET=1/0`` forces interpreter/compiled dispatch
(default: compiled only on TPU).  Compiled kernels are cached per
padded static shape in a bounded LRU (:data:`_RUN_CACHE`, capacity
:data:`_RUN_CACHE_MAX`); eviction only drops a compiled artifact — a
rebuilt kernel is deterministic, so results never change.  Batch sizes
are bucketed to powers of two so a schedule compiles O(log max_batch)
kernel variants, not one per wave width.

Unlike the NumPy vector backend, masked per-hop reads/writes do not
require link-disjoint routes: hops are walked sequentially, so a route
may revisit a link.

**Whole-schedule scan path** (the default, DESIGN.md §5): on top of the
per-wave kernel this module also folds the *entire* wave plan into one
jitted ``lax.scan`` dispatch (``evaluate_plan``).  The engine emits the
complete level-batched plan up front (``engine.plan_waves``); the host
stages stacked per-wave inputs (task ids, predecessor ids + edge
indices, exit/real flags) plus the all-source route tensors
(``layout.stacked_src_tensors``) and the all-edge CTML table, uploaded
factored by each source's distinct hop speeds
(``layout.factored_edge_ct``) and expanded on the device
(:func:`repro_ct_expand`, the same bits), and the scan
body — pure ``jnp``, the exact op-for-op algebra of the per-wave kernel
— carries ``(link_free, proc_free, loads, loads/period, BP, aft,
proc_of)`` wave to wave, sorting each decision's predecessors by the
device-resident ``(aft, id)`` key (``jnp.lexsort``) and gathering their
source rows dynamically.  One upload, one launch, one blocking fetch
per schedule: host round-trips drop O(levels) -> O(1).  The HVLB_CC
alpha sweep folds in as one more batch axis (``evaluate_plan_sweep``):
a ``vmap`` over the alpha grid evaluates every alpha's schedule in the
same dispatch; the host keeps each decision's winner row, reads every
alpha's EFTs from it, and decodes an alpha's decisions only when they
are read.  ``REPRO_PALLAS_SCAN=0`` falls back to the per-wave
kernel loop (which also serves single-decision ``evaluate`` protocol
calls and remains the numerics reference for the scan).

``n_launches`` / ``n_roundtrips`` / ``n_state_uploads`` count kernel
launches, blocking device->host transfers, and host->device state
re-uploads; ``benchmarks/exp7`` records launches per schedule and the
CI gate holds the per-schedule total at a constant (<= 3: upload,
dispatch, fetch) on the scan path and O(levels) on the per-wave path.
With :mod:`repro.tracing` on, the scan path's set-up, table upload,
staging, launch, fetch and decode are spans and the ``backend.*``
counters count launches and bytes each way (DESIGN.md §10).
"""
from __future__ import annotations

import contextlib
import functools
import os
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ... import tracing
from .base import CandidateEvaluator, Decision, PlanSweep
from ..faults import WaveTimeoutError
from .layout import (CT_COL0, CT_ZERO, LANE, SUBLANE_F32, bucket,
                     factored_edge_ct, pad_dim, padded_edge_ct,
                     padded_src_tensors, src_layout, stacked_src_tensors)

_INF = float("inf")
_NEG_INF = float("-inf")

# Documented f32 near-tie tolerance: two candidates whose selection
# values agree within this *relative* tolerance may resolve differently
# from the f64 scalar reference on the float32 device path (the winner
# is then the deterministic f32 argmin).  Chosen ~2 decades above the
# f32 epsilon (1.19e-7) so accumulated rounding across a schedule's
# worth of in-kernel commits stays inside it.
F32_NEAR_TIE_RTOL = 1e-5

# jitted kernel wrappers keyed by the padded static shape signature
# (B, K, R, H, P, L, f32?, interpret?): instances with the same padded
# dims share one trace/compile.  Bounded LRU — each entry pins a traced/
# compiled XLA executable, and a long-lived process scheduling many
# distinctly-shaped graphs would otherwise grow it forever.  Eviction is
# safe: rebuilding a kernel is deterministic, results never change.
_RUN_CACHE: "OrderedDict[tuple, object]" = OrderedDict()
_RUN_CACHE_MAX = 32


def _use_interpret() -> bool:
    """Interpreter-mode fallback: compiled Mosaic kernels need a TPU;
    everywhere else (CPU CI runners, GPU hosts) the kernel runs under
    the Pallas interpreter.  ``REPRO_PALLAS_INTERPRET=1/0`` forces."""
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() != "tpu"


def _use_f32(interpret: bool) -> bool:
    """Kernel dtype: float32 on the compiled path (TPUs have no f64),
    float64 under the interpreter (keeps the scalar-reference arithmetic
    bit-for-bit).  ``REPRO_PALLAS_DTYPE=float32|float64`` forces — the
    f32 near-tie policy is tested by forcing f32 under the interpreter."""
    env = os.environ.get("REPRO_PALLAS_DTYPE")
    if env is not None:
        if env in ("float32", "f32"):
            return True
        if env in ("float64", "f64"):
            return False
        raise ValueError(f"REPRO_PALLAS_DTYPE={env!r}: expected float32 "
                         "or float64")
    return not interpret


def _use_tile(interpret: bool) -> bool:
    """Tile padding: on for a real Mosaic compile (P to sublane, L to
    lane multiples), off under the interpreter where it only costs time.
    ``REPRO_PALLAS_TILE=1/0`` forces (padding is arithmetic-neutral, so
    the padded shapes are exercised under the interpreter in CI)."""
    env = os.environ.get("REPRO_PALLAS_TILE")
    if env is not None:
        return env not in ("0", "false", "False")
    return not interpret


def _use_scan() -> bool:
    """Whole-schedule ``lax.scan`` dispatch (one launch per schedule)
    vs the per-wave kernel loop.  On by default — the two paths are
    decision-identical (f64) / near-tie-policy-identical (f32);
    ``REPRO_PALLAS_SCAN=0`` forces the per-wave loop (exp7 uses the
    toggle to time both)."""
    env = os.environ.get("REPRO_PALLAS_SCAN")
    if env is not None:
        return env not in ("0", "false", "False")
    return True


def _x64(f32: bool):
    """Scope for one f64 upload/dispatch: without ``jax.enable_x64``
    jnp and jit canonicalize float64 inputs (and the kernel trace) down
    to float32.  The f32 path needs no switch."""
    return contextlib.nullcontext() if f32 else jax.enable_x64(True)


def _batch_kernel(alpha_ref, period_ref, aft_ref, ct_ref, masks_ref,
                  valid_ref, nhops_ref, comp_ref, ldet_ref, flags_ref,
                  lf0_ref, pf0_ref, loads0_ref, lop0_ref, bp0_ref,
                  win_ref, est_ref, eft_ref, a_ref, b_ref,
                  lst_ref, lft_ref, bestr_ref,
                  lf_ref, pf_ref, loads_ref, lop_ref, bp_ref,
                  *, K: int, R: int, H: int, P: int, L: int):
    """One grid step = one decision of the wave (module docstring).

    The wave is a ``grid=(B,)`` launch: TPU (and interpreter) grids
    iterate **sequentially**, so the link/processor state committed by
    grid step ``b`` is exactly what step ``b+1`` reads — the carry lives
    in the state *output* blocks (``lf_ref`` ... ``bp_ref``), whose
    constant index map revisits the same VMEM block every step; step 0
    seeds them from the state inputs.  Per-decision inputs/outputs are
    blocked on the leading (decision) axis, so the traced body is
    independent of the wave width B.

    Static shapes: K padded predecessors x R padded routes x H padded
    hops over (P, L) tile-padded lanes/links; loops unroll at trace
    time.  Padding is arithmetic, not control flow: padded hops read
    ``-inf`` and add ``-inf`` CTML (the running maxima ignore them),
    padded routes mask to ``+inf`` arrival, padded predecessors carry
    ``aft = -inf`` and all-zero commit masks, padded processor lanes
    carry ``+inf`` computation cost (never win), and padded *decisions*
    (bucket tail) carry ``is_real = 0`` so their commit is a no-op —
    every padded contribution drops out of the exact max algebra.

    ``flags_ref[0] = (is_exit, is_real)``: exit tasks pass ``ldet = 1``
    rows and select on bare EFT (``BP`` forced to 1, so ``eft * 1 * 1``
    collapses exactly to the Def. 4.2 value).
    """
    f = lf0_ref.dtype
    neg = jnp.array(_NEG_INF, dtype=f)
    one = jnp.array(1.0, dtype=f)
    alpha = alpha_ref[0]
    period = period_ref[0]
    first = pl.program_id(0) == 0
    # state carry: seeded from the inputs at step 0, thereafter read
    # back from the revisited output blocks (select discards whatever
    # the unselected branch read, so the uninitialized step-0 output
    # read is harmless)
    lf = jnp.where(first, lf0_ref[:], lf_ref[:])
    pf = jnp.where(first, pf0_ref[:], pf_ref[:])
    loads = jnp.where(first, loads0_ref[:], loads_ref[:])
    lop = jnp.where(first, lop0_ref[:], lop_ref[:])
    bp = jnp.where(first, bp0_ref[:], bp_ref[:])
    idx = jax.lax.broadcasted_iota(jnp.int32, (P, 1), 0)[:, 0]

    lane = jnp.broadcast_to(lf, (P, L))
    arrival = jnp.full((P,), _NEG_INF, dtype=f)
    for k in range(K):
        aft_i = aft_ref[0, 0, k]
        r_lst = []
        r_lft = []
        r_final = []
        for r in range(R):
            lst = lft = None
            lsts = []
            lfts = []
            for h in range(H):
                m = masks_ref[0, k, r, h]                # (P, L) one-hot
                avail = jnp.max(jnp.where(m > 0, lane, neg), axis=1)
                lst = jnp.maximum(avail, aft_i) if h == 0 \
                    else jnp.maximum(lst, avail)         # Eq. 13
                x = lst + ct_ref[0, k, r, h]
                lft = x if h == 0 else jnp.maximum(lft, x)   # Eq. 14
                lsts.append(lst)
                lfts.append(lft)
            r_lst.append(lsts)
            r_lft.append(lfts)
            r_final.append(jnp.where(valid_ref[0, k, r] > 0, lft, _INF))
        # lexicographic (LFT, hops, route-index) min per lane
        best_f = r_final[0]
        best_nh = nhops_ref[0, k, 0]
        best_r = jnp.zeros((P,), jnp.int32)
        for r in range(1, R):
            fv = r_final[r]
            nh = nhops_ref[0, k, r]
            better = (fv < best_f) | ((fv == best_f) & (nh < best_nh))
            best_f = jnp.where(better, fv, best_f)
            best_nh = jnp.where(better, nh, best_nh)
            best_r = jnp.where(better, jnp.int32(r), best_r)
        # commit the selected route per lane; LFT_h >= avail_h, so a
        # masked overwrite reproduces the scalar "write if greater"
        for h in range(H):
            sel_lst = r_lst[0][h]
            sel_lft = r_lft[0][h]
            sel_m = masks_ref[0, k, 0, h]
            for r in range(1, R):
                pick = best_r == r
                sel_lst = jnp.where(pick, r_lst[r][h], sel_lst)
                sel_lft = jnp.where(pick, r_lft[r][h], sel_lft)
                sel_m = jnp.where(pick[:, None],
                                  masks_ref[0, k, r, h], sel_m)
            lane = jnp.where(sel_m > 0, sel_lft[:, None], lane)
            lst_ref[0, k, h, :] = sel_lst
            lft_ref[0, k, h, :] = sel_lft
        bestr_ref[0, k, :] = best_r
        arrival = jnp.maximum(arrival, best_f)

    # ---- batched Eqs. 10-12 + Defs. 4.1-4.2 over all P lanes ----
    est = jnp.maximum(arrival, pf)                       # Eqs. 10-11
    comp = comp_ref[0, 0]
    eft = est + comp                                     # Eq. 12
    a = eft * ldet_ref[0, 0]
    is_exit = flags_ref[0, 0, 0] > 0
    value = a * jnp.where(is_exit, one, bp)  # Def. 4.1 (exit: ldet=bp=1)
    # strict lexicographic (value, eft, proc) argmin, first-index ties
    vmin = jnp.min(value)
    tie = value == vmin
    emin = jnp.min(jnp.where(tie, eft, _INF))
    tie &= eft == emin
    w = jnp.min(jnp.where(tie, idx, jnp.int32(P)))
    win_ref[0, 0, :] = jnp.broadcast_to(w, (1,))
    est_ref[0, 0, :] = est
    eft_ref[0, 0, :] = eft
    a_ref[0, 0, :] = a
    b_ref[0, 0, :] = a * lop         # pre-commit loads/period, as scalar
    # ---- in-kernel commit (the next grid step reads this state) ----
    real = flags_ref[0, 0, 1] > 0
    onehot = (idx == w) & real
    # the winner lane's column of the lane buffer IS the committed
    # link state: masked overwrites only ever raise (LFT >= avail),
    # so the column equals the scalar path's max-folded commits
    win_col = jnp.max(jnp.where(onehot[:, None], lane, neg), axis=0)
    lf_ref[:] = jnp.where(real, win_col, lf)
    pf_ref[:] = jnp.where(onehot, eft, pf)
    loads = jnp.where(onehot, loads + comp, loads)
    loads_ref[:] = loads
    lop = jnp.where(onehot, loads / period, lop)
    lop_ref[:] = lop
    bp_ref[:] = jnp.where(onehot, one + lop * alpha, bp)


def _compiled_run(B: int, K: int, R: int, H: int, P: int, L: int,
                  f32: bool, interpret: bool):
    key = (B, K, R, H, P, L, f32, interpret)
    run = _RUN_CACHE.pop(key, None)
    if run is None:
        kern = functools.partial(_batch_kernel, K=K, R=R, H=H, P=P, L=L)
        f = jnp.float32 if f32 else jnp.float64
        i32 = jnp.int32
        full = lambda *shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))  # noqa: E731
        dec = lambda *shape: pl.BlockSpec((1,) + shape,  # noqa: E731
                                          lambda i: (i,) + (0,) * len(shape))
        # Mosaic blocks must match the array in their last two dims (or
        # tile them): per-decision rows of rank < 2 get a unit axis, so
        # a (B, 1, X) array is blocked (1, 1, X)
        in_specs = [
            full(1), full(1),                        # alpha, period
            dec(1, K),                               # aft
            dec(K, R, H, P),                         # ct
            dec(K, R, H, P, L),                      # masks
            dec(K, R, P), dec(K, R, P),              # valid, nhops
            dec(1, P), dec(1, P),                    # comp, ldet
            dec(1, 2),                               # (is_exit, is_real)
            full(L), full(P), full(P), full(P), full(P),   # state in
        ]
        out_specs = (
            dec(1, 1),                               # winner lane
            dec(1, P), dec(1, P), dec(1, P), dec(1, P),    # est, eft, A, B
            dec(K, H, P), dec(K, H, P),              # selected LST/LFT
            dec(K, P),                               # selected route
            full(L), full(P), full(P), full(P), full(P),   # state carry
        )
        out_shape = (
            jax.ShapeDtypeStruct((B, 1, 1), i32),    # winner lane
            jax.ShapeDtypeStruct((B, 1, P), f),      # est
            jax.ShapeDtypeStruct((B, 1, P), f),      # eft
            jax.ShapeDtypeStruct((B, 1, P), f),      # cand_A
            jax.ShapeDtypeStruct((B, 1, P), f),      # cand_B
            jax.ShapeDtypeStruct((B, K, H, P), f),   # selected LST
            jax.ShapeDtypeStruct((B, K, H, P), f),   # selected LFT
            jax.ShapeDtypeStruct((B, K, P), i32),    # selected route
            jax.ShapeDtypeStruct((L,), f),           # link state carry
            jax.ShapeDtypeStruct((P,), f),           # proc_free carry
            jax.ShapeDtypeStruct((P,), f),           # loads carry
            jax.ShapeDtypeStruct((P,), f),           # loads/period carry
            jax.ShapeDtypeStruct((P,), f),           # BP carry
        )
        call = pl.pallas_call(kern, grid=(B,), in_specs=in_specs,
                              out_specs=out_specs, out_shape=out_shape,
                              interpret=interpret)

        def run(alpha, period, aft, cts, masks, valids, nhopss,
                comp, ldet, flags, lf, pf, loads, lop, bp):
            ct = jnp.stack(cts).reshape(B, K, R, H, P)
            m = jnp.stack(masks).reshape(B, K, R, H, P, L)
            v = jnp.stack(valids).reshape(B, K, R, P)
            nh = jnp.stack(nhopss).reshape(B, K, R, P)
            out = call(alpha, period, aft.reshape(B, 1, K), ct, m, v, nh,
                       comp.reshape(B, 1, P), ldet.reshape(B, 1, P),
                       flags.reshape(B, 1, 2), lf, pf, loads, lop, bp)
            rows = tuple(x.reshape(B, *x.shape[2:]) for x in out[1:5])
            return (out[0].reshape(B),) + rows + tuple(out[5:])

        run = jax.jit(run)
    _RUN_CACHE[key] = run
    while len(_RUN_CACHE) > _RUN_CACHE_MAX:
        _RUN_CACHE.popitem(last=False)
    return run


def _scan_run(W: int, B: int, K: int, R: int, H: int, Pp: int, Lp: int,
              Np: int, Ep: int, A: int, f32: bool):
    """Jitted whole-schedule runner: ``lax.scan`` over ``W`` stacked
    waves of ``B`` decision slots (module docstring; DESIGN.md §5).

    Cached per **padded** static signature — ``(W, B)`` bucketed wave
    count/width, predecessor/route/hop maxima, tile-padded ``(Pp, Lp)``,
    bucketed task/edge counts ``(Np, Ep)``, and the bucketed alpha-grid
    width ``A`` (0 = no sweep axis) — so graphs with the same padded
    shape share one compilation.

    The scan body replays the per-wave kernel's algebra op for op; the
    only new arithmetic is *ordering*, not values: each slot sorts its
    predecessors by the device-carried ``(aft, id)`` key (the scalar
    reference's host-side sort — unknowable on the host here because a
    predecessor's AFT is decided inside the scan) and gathers that
    predecessor's route tensors by its carried placement.  Padded slots
    (``real = 0``), padded waves (all-pad rows) and padded predecessors
    (pad source plane ``P``, pad edge row ``Ep - 1``) drop out of the
    exact max algebra exactly like the per-wave pad tensors.

    With ``A > 0`` the whole scan is ``vmap``-ed over a ``(A,)`` alpha
    vector — the (A, B) fused sweep grid: every alpha's schedule
    evolves its own independent carry inside the same dispatch.
    """
    key = ("scan", W, B, K, R, H, Pp, Lp, Np, Ep, A, f32)
    run = _RUN_CACHE.pop(key, None)
    if run is not None:
        _RUN_CACHE[key] = run
        return run
    f = jnp.float32 if f32 else jnp.float64
    i32 = jnp.int32

    # the jitted functions' names name the XLA module on the device
    # trace (``jit_repro_scan`` / ``jit_repro_scan_sweep``)
    def repro_scan(alpha, period, task, real, pred, pvalid, edge, exitf,
                   masks_all, valid_all, nhops_all, ct_all, comp_all,
                   ldet_all, lf0, pf0, loads0, lop0, bp0, aft0, proc0):
        one = jnp.array(1.0, dtype=f)
        neg = jnp.array(_NEG_INF, dtype=f)
        pad_src = jnp.int32(masks_all.shape[0] - 1)
        idx = jax.lax.broadcasted_iota(jnp.int32, (Pp, 1), 0)[:, 0]

        def wave_step(carry, xs):
            lf, pf, loads, lop, bp, aft_t, proc_t = carry
            w_task, w_real, w_pred, w_pvalid, w_edge, w_exit = xs

            def slot(b, st):
                (lf, pf, loads, lop, bp, aft_t, proc_t,
                 win_o, est_o, eft_o, a_o, b_o, lst_o, lft_o,
                 bestr_o) = st
                j = w_task[b]
                is_real = w_real[b] > 0
                is_exit = w_exit[b] > 0
                pv = w_pvalid[b] > 0
                # the scalar reference's (aft, id) predecessor order,
                # computed on device from the carried AFT; invalid slots
                # sort last on the (+inf, Np) key and read the pad
                # source plane / pad edge row
                paft = jnp.where(pv, aft_t[w_pred[b]], _INF)
                pkey = jnp.where(pv, w_pred[b], jnp.int32(Np))
                perm = jnp.lexsort((pkey, paft))
                sp = w_pred[b][perm]
                spv = pv[perm]
                s_aft = jnp.where(spv, aft_t[sp], neg)
                s_src = jnp.where(spv, proc_t[sp], pad_src)
                s_edge = jnp.where(spv, w_edge[b][perm], jnp.int32(Ep - 1))

                comp_j = comp_all[j]
                ldet_j = ldet_all[j]
                lane = jnp.broadcast_to(lf, (Pp, Lp))
                arrival = jnp.full((Pp,), _NEG_INF, dtype=f)
                sel_lsts = []
                sel_lfts = []
                bestrs = []
                for k in range(K):
                    aft_i = s_aft[k]
                    m_k = masks_all[s_src[k]]
                    ct_k = ct_all[s_edge[k], s_src[k]]
                    v_k = valid_all[s_src[k]]
                    nh_k = nhops_all[s_src[k]]
                    r_lst = []
                    r_lft = []
                    r_final = []
                    for r in range(R):
                        lst = lft = None
                        lsts = []
                        lfts = []
                        for h in range(H):
                            m = m_k[r, h]                    # (Pp, Lp)
                            avail = jnp.max(jnp.where(m > 0, lane, neg),
                                            axis=1)
                            lst = jnp.maximum(avail, aft_i) if h == 0 \
                                else jnp.maximum(lst, avail)     # Eq. 13
                            x = lst + ct_k[r, h]
                            lft = x if h == 0 else jnp.maximum(lft, x)
                            lsts.append(lst)
                            lfts.append(lft)
                        r_lst.append(lsts)
                        r_lft.append(lfts)
                        r_final.append(jnp.where(v_k[r] > 0, lft, _INF))
                    best_f = r_final[0]
                    best_nh = nh_k[0]
                    best_r = jnp.zeros((Pp,), jnp.int32)
                    for r in range(1, R):
                        fv = r_final[r]
                        nh = nh_k[r]
                        better = (fv < best_f) | ((fv == best_f) &
                                                  (nh < best_nh))
                        best_f = jnp.where(better, fv, best_f)
                        best_nh = jnp.where(better, nh, best_nh)
                        best_r = jnp.where(better, jnp.int32(r), best_r)
                    sl = []
                    sf = []
                    for h in range(H):
                        sel_lst = r_lst[0][h]
                        sel_lft = r_lft[0][h]
                        sel_m = m_k[0, h]
                        for r in range(1, R):
                            pick = best_r == r
                            sel_lst = jnp.where(pick, r_lst[r][h], sel_lst)
                            sel_lft = jnp.where(pick, r_lft[r][h], sel_lft)
                            sel_m = jnp.where(pick[:, None], m_k[r, h],
                                              sel_m)
                        lane = jnp.where(sel_m > 0, sel_lft[:, None], lane)
                        sl.append(sel_lst)
                        sf.append(sel_lft)
                    sel_lsts.append(jnp.stack(sl))
                    sel_lfts.append(jnp.stack(sf))
                    bestrs.append(best_r)
                    arrival = jnp.maximum(arrival, best_f)

                est = jnp.maximum(arrival, pf)               # Eqs. 10-11
                eft = est + comp_j                           # Eq. 12
                a = eft * ldet_j
                value = a * jnp.where(is_exit, one, bp)      # Def. 4.2
                vmin = jnp.min(value)
                tie = value == vmin
                emin = jnp.min(jnp.where(tie, eft, _INF))
                tie &= eft == emin
                w = jnp.min(jnp.where(tie, idx, jnp.int32(Pp)))
                cb = a * lop         # pre-commit loads/period, as scalar
                onehot = (idx == w) & is_real
                win_col = jnp.max(jnp.where(onehot[:, None], lane, neg),
                                  axis=0)
                lf = jnp.where(is_real, win_col, lf)
                pf = jnp.where(onehot, eft, pf)
                loads = jnp.where(onehot, loads + comp_j, loads)
                lop = jnp.where(onehot, loads / period, lop)
                bp = jnp.where(onehot, one + lop * alpha, bp)  # Def. 4.1
                eft_w = eft[w]
                aft_t = aft_t.at[j].set(jnp.where(is_real, eft_w,
                                                  aft_t[j]))
                proc_t = proc_t.at[j].set(jnp.where(is_real, w,
                                                    proc_t[j]))
                win_o = win_o.at[b].set(w)
                est_o = est_o.at[b].set(est)
                eft_o = eft_o.at[b].set(eft)
                a_o = a_o.at[b].set(a)
                b_o = b_o.at[b].set(cb)
                lst_o = lst_o.at[b].set(jnp.stack(sel_lsts))
                lft_o = lft_o.at[b].set(jnp.stack(sel_lfts))
                bestr_o = bestr_o.at[b].set(jnp.stack(bestrs))
                return (lf, pf, loads, lop, bp, aft_t, proc_t,
                        win_o, est_o, eft_o, a_o, b_o, lst_o, lft_o,
                        bestr_o)

            st = (lf, pf, loads, lop, bp, aft_t, proc_t,
                  jnp.zeros((B,), i32),
                  jnp.zeros((B, Pp), f), jnp.zeros((B, Pp), f),
                  jnp.zeros((B, Pp), f), jnp.zeros((B, Pp), f),
                  jnp.zeros((B, K, H, Pp), f),
                  jnp.zeros((B, K, H, Pp), f),
                  jnp.zeros((B, K, Pp), i32))
            st = jax.lax.fori_loop(0, B, slot, st)
            lf, pf, loads, lop, bp, aft_t, proc_t = st[:7]
            return (lf, pf, loads, lop, bp, aft_t, proc_t), st[7:]

        carry0 = (lf0, pf0, loads0, lop0, bp0, aft0, proc0)
        xs = (task, real, pred, pvalid, edge, exitf)
        # the body is traced inside the scope: it names the wave's
        # operations in the op metadata
        with jax.named_scope("repro.scan.wave"):
            _, ys = jax.lax.scan(wave_step, carry0, xs)
        return ys

    if A:
        def repro_scan_sweep(alphas, period, task, real, pred, pvalid,
                             edge, exitf, masks_all, valid_all, nhops_all,
                             ct_all, comp_all, ldet_all, lf0, pf0, loads0,
                             lop0, bp0, aft0, proc0):
            def one(al):
                return repro_scan(al, period, task, real, pred, pvalid,
                                  edge, exitf, masks_all, valid_all,
                                  nhops_all, ct_all, comp_all, ldet_all,
                                  lf0, pf0, loads0, lop0, bp0, aft0, proc0)
            return jax.vmap(one)(alphas)

        run = jax.jit(repro_scan_sweep)
    else:
        run = jax.jit(repro_scan)
    _RUN_CACHE[key] = run
    while len(_RUN_CACHE) > _RUN_CACHE_MAX:
        _RUN_CACHE.popitem(last=False)
    return run


@jax.jit
def repro_ct_expand(q: jax.Array, idx: jax.Array,
                    n_edges: jax.Array) -> jax.Array:
    """The scan's ``(Ep, P + 1, R, H, Pp)`` edge CTML table from its
    factored upload (``layout.factored_edge_ct``): ``ct[e, s, r, h, p]``
    is ``q[e, s, u]`` where ``idx[s, r, h, p]`` is ``CT_COL0 + u``,
    ``-inf`` at ``CT_NEG``, and at ``CT_ZERO`` 0.0 on the ``n_edges``
    real edge rows and ``-inf`` on the pad rows.  Selects of the
    uploaded floats, so the table's bits are the host build's.  Its XLA
    module is ``jit_repro_ct_expand`` on the device trace."""
    Ep, S, U = q.shape
    neg = jnp.array(_NEG_INF, q.dtype)
    ct = jnp.full((Ep, S) + idx.shape[1:], neg)
    zero = jnp.where(jnp.arange(Ep) < n_edges, jnp.zeros((), q.dtype), neg)
    ct = jnp.where(idx == CT_ZERO, zero[:, None, None, None, None], ct)
    for u in range(U):
        ct = jnp.where(idx == CT_COL0 + u, q[:, :, u, None, None, None], ct)
    return ct


def _gather_winners(outs: tuple, waves: Sequence[Sequence[int]], A: int,
                    P: int) -> tuple:
    """Each real decision's row of fetched scan outputs at its winning
    processor, in queue order (the waves flattened), for the first ``A``
    entries of the leading alpha axis: ``win``, ``est``, ``eft`` (A, q);
    ``ca``, ``cb`` over the real processors (A, q, P); ``lst``, ``lft``
    (A, q, K, H); ``bestr`` (A, q, K).  Padded alphas, waves, slots and
    processor lanes are dropped, and advanced indexing copies, so the
    padded fetch can be freed."""
    win, est, eft, ca, cb, lst, lft, bestr = outs
    wv = np.repeat(np.arange(len(waves)), [len(w) for w in waves])
    b = np.concatenate([np.arange(len(w)) for w in waves])
    a = np.arange(A)[:, None]
    p = win[:A, wv, b]
    return (p, est[a, wv, b, p], eft[a, wv, b, p], ca[:A, wv, b, :P],
            cb[:A, wv, b, :P], lst[a, wv, b, :, :, p],
            lft[a, wv, b, :, :, p], bestr[a, wv, b, :, p])


class PallasBackend(CandidateEvaluator):
    """Device-batched candidate evaluation: one Pallas kernel per wave."""

    name = "pallas"

    @tracing.traced("repro.backend.build")
    def __init__(self, inst) -> None:
        super().__init__(inst)
        self._interpret = _use_interpret()
        self._f32 = _use_f32(self._interpret)
        self._tile = _use_tile(self._interpret)
        self._np_dtype = np.float32 if self._f32 else np.float64
        self._dtype = jnp.float32 if self._f32 else jnp.float64
        P = inst.P
        self._L = L = max(1, inst._n_links)
        # instance-global padded dims so per-pred tensors stack; tile
        # padding (sublane P, lane L) only when targeting Mosaic
        lays = [src_layout(inst, s) for s in range(P)]
        self._R = max(l.R for l in lays)
        self._H = max(l.H for l in lays)
        self._K = max([1] + [len(p) for p in inst._preds])
        self._Pp = pad_dim(P, SUBLANE_F32) if self._tile else P
        self._Lp = pad_dim(L, LANE) if self._tile else L
        self._src_dev: Dict[int, Tuple[jax.Array, jax.Array, jax.Array]] = {}
        self._ct_dev: Dict[Tuple[int, int, int], jax.Array] = {}
        # padding predecessor: aft = -inf, zero masks, -inf CTML, one
        # valid zero-hop route -> arrival/commit no-ops
        R, H, Pp, Lp = self._R, self._H, self._Pp, self._Lp
        pad_ct = np.full((R, H, Pp), _NEG_INF)
        pad_valid = np.zeros((R, Pp))
        pad_valid[0] = 1.0
        self._pad = (self._to_dev(pad_ct),
                     self._to_dev(np.zeros((R, H, Pp, Lp))),
                     self._to_dev(pad_valid),
                     self._to_dev(np.zeros((R, Pp))))
        if tracing.enabled():
            tracing.count("backend.h2d_bytes",
                          sum(a.nbytes for a in self._pad))
        # comp rows padded with +inf lanes (padded lanes never win);
        # ldet rows: exit tasks and padded lanes read exactly 1.0
        comp_pad = np.full((inst.n, Pp), _INF)
        comp_pad[:, :P] = inst.comp
        ldet_pad = np.ones((inst.n, Pp))
        ldet_pad[:, :P] = inst.ldet
        ldet_pad[inst._is_exit, :] = 1.0
        self._comp_rows = comp_pad.astype(self._np_dtype)
        self._ldet_rows = ldet_pad.astype(self._np_dtype)
        # scan-path consts: bucketed task/edge axes for the carried
        # aft/proc arrays and the stacked all-edge CT table; the device
        # stacks themselves are built lazily on the first plan dispatch
        self._Np = bucket(inst.n)
        self._Ep = bucket(len(inst._edge_index) + 1)
        self._scan_dev: Optional[tuple] = None
        self._scan_in_cache: "OrderedDict[tuple, tuple]" = OrderedDict()
        # instrumentation (read by benchmarks/exp7 and the tests)
        self.n_launches = 0
        self.n_roundtrips = 0
        self.n_state_uploads = 0

    # ------------------------------------------------------------ device
    def _to_dev(self, arr: np.ndarray) -> jax.Array:
        """Upload a float array in the kernel dtype (f64 needs the scoped
        x64 switch so jnp does not silently truncate)."""
        arr = np.asarray(arr, dtype=self._np_dtype)
        with _x64(self._f32):
            return jnp.asarray(arr)

    # ------------------------------------------------------------- state
    def _alloc(self) -> None:
        inst = self.inst
        P, L = inst.P, self._L
        self.link_free = np.zeros(L, dtype=np.float64)   # host mirror
        self.proc_free = np.zeros(P, dtype=np.float64)
        self.loads = np.zeros(P, dtype=np.float64)
        self._lop = np.zeros(P, dtype=np.float64)
        self._bp = np.ones(P, dtype=np.float64)
        # device state carry (link_free, proc_free, loads, loads/period,
        # BP) — built from the host mirrors on first use and after any
        # host-side commit (trace replay), then carried launch-to-launch
        self._state: Optional[tuple] = None
        self._state_dirty = True

    def _upload_state(self) -> None:
        """(Re)build the device state carry from the float64 host
        mirrors — one transfer, paid at run start and after a trace
        replay committed host-side (on the f64 path mirrors and device
        state are bit-equal, so the re-upload is value-neutral)."""
        P, Pp, L, Lp = self.inst.P, self._Pp, self._L, self._Lp
        lf = np.zeros(Lp)
        lf[:L] = self.link_free
        pf = np.zeros(Pp)
        pf[:P] = self.proc_free
        loads = np.zeros(Pp)
        loads[:P] = self.loads
        lop = np.zeros(Pp)
        lop[:P] = self._lop
        bp = np.ones(Pp)
        bp[:P] = self._bp
        self._state = tuple(self._to_dev(x)
                            for x in (lf, pf, loads, lop, bp))
        self._state_dirty = False
        self.n_state_uploads += 1

    def _commit_host(self, j: int, p: int, est: float, eft: float,
                     msgs: list) -> None:
        """Mirror one in-kernel commit on the host: the shared scalar
        ``apply`` plus the incremental Def.-4.1 terms — same floats in
        the same order as any other backend, which is what keeps traces
        recorded here replayable anywhere."""
        CandidateEvaluator.apply(self, j, p, est, eft, msgs)
        lop = self.loads[p] / self.period
        self._lop[p] = lop
        self._bp[p] = 1.0 + lop * self.alpha

    def apply(self, j: int, p: int, est: float, eft: float,
              msgs: list) -> None:
        """Trace-replay commit: host mirrors only; the device carry is
        marked stale and re-uploaded wholesale before the next launch
        (replaying n records costs one transfer, not n scatters)."""
        self._commit_host(j, p, est, eft, msgs)
        self._state_dirty = True

    # ----------------------------------------------------- device consts
    def _src_tensors(self, src: int):
        """One-hot hop masks + route validity/hop counts of ``src``,
        padded to the instance-global (R, H, Pp, Lp) and device-resident
        (shaped by the shared ``layout`` precompute, uploaded once)."""
        dev = self._src_dev.get(src)
        if dev is None:
            masks, valid, nhops = padded_src_tensors(
                self.inst, src, self._R, self._H, self._Pp, self._Lp)
            dev = (self._to_dev(masks), self._to_dev(valid),
                   self._to_dev(nhops))
            self._src_dev[src] = dev
        return dev

    def _edge_tensor(self, i: int, j: int, src: int) -> jax.Array:
        """Device CTML tensor (R, H, Pp) of edge ``e_ij`` from ``src``,
        a padded view of the shared all-edge table, uploaded once."""
        ct = self._ct_dev.get((i, j, src))
        if ct is None:
            ct = self._to_dev(padded_edge_ct(
                self.inst, self.inst._src_layouts[src], i, j,
                self._R, self._H, self._Pp))
            self._ct_dev[(i, j, src)] = ct
        return ct

    # ---------------------------------------------------------- evaluate
    def _run_batch(self, js: Sequence[int], commit: bool) -> List[Decision]:
        """Stage one wave, launch one kernel, decode one transfer."""
        inst = self.inst
        P = inst.P
        aft = self.aft
        proc_of = self.proc_of
        K = self._K
        if self._state_dirty:
            self._upload_state()

        B = len(js)
        Bp = bucket(B)
        pad_ct, pad_masks, pad_valid, pad_nhops = self._pad
        cts, masks, valids, nhopss = [], [], [], []
        aft_rows = np.full((Bp, K), _NEG_INF)
        flags = np.zeros((Bp, 2))
        preds_of: List[list] = []
        srcs_of: List[list] = []
        comp_rows = np.empty((Bp, self._Pp), dtype=self._np_dtype)
        ldet_rows = np.ones((Bp, self._Pp), dtype=self._np_dtype)
        for b, j in enumerate(js):
            preds = inst._preds[j]
            if len(preds) > 1:
                preds = sorted(preds, key=lambda i: (aft[i], i))
            srcs = [proc_of[i] for i in preds]
            preds_of.append(preds)
            srcs_of.append(srcs)
            for k, (i, src) in enumerate(zip(preds, srcs)):
                m, v, nh = self._src_tensors(src)
                cts.append(self._edge_tensor(i, j, src))
                masks.append(m)
                valids.append(v)
                nhopss.append(nh)
                aft_rows[b, k] = aft[i]
            for _ in range(K - len(preds)):
                cts.append(pad_ct)
                masks.append(pad_masks)
                valids.append(pad_valid)
                nhopss.append(pad_nhops)
            comp_rows[b] = self._comp_rows[j]
            ldet_rows[b] = self._ldet_rows[j]
            flags[b, 0] = 1.0 if inst._is_exit[j] else 0.0
            flags[b, 1] = 1.0 if commit else 0.0
        if Bp > B:                       # bucket padding: no-op decisions
            # finite comp rows keep the padded winner math inf-free; the
            # is_real = 0 flag (zeros-initialized) voids their commit
            comp_rows[B:] = self._comp_rows[js[0]]
            ldet_rows[B:] = 1.0
            for _ in range((Bp - B) * K):
                cts.append(pad_ct)
                masks.append(pad_masks)
                valids.append(pad_valid)
                nhopss.append(pad_nhops)

        run = _compiled_run(Bp, K, self._R, self._H, self._Pp, self._Lp,
                            self._f32, self._interpret)
        dt = self._np_dtype
        args = (np.asarray([self.alpha], dtype=dt),
                np.asarray([self.period], dtype=dt),
                aft_rows.astype(dt), tuple(cts), tuple(masks),
                tuple(valids), tuple(nhopss), comp_rows, ldet_rows,
                flags.astype(dt), *self._state)
        with _x64(self._f32):
            out = run(*args)
        self.n_launches += 1
        if commit:
            # the state carry stays on device — never fetched
            self._state = tuple(out[8:])
        win, est, eft, ca_all, cb_all, lst, lft, bestr = \
            jax.device_get(out[:8])  # analysis: allow[host-sync] the documented one-per-wave transfer (DESIGN.md §5); state carry stays on device
        self.n_roundtrips += 1

        decisions: List[Decision] = []
        for b, j in enumerate(js):
            p = int(win[b])
            msgs = []
            for k, (i, src) in enumerate(zip(preds_of[b], srcs_of[b])):
                if src == p:
                    continue
                r = int(bestr[b, k, p])
                lids, robj = inst._src_layouts[src].route_meta[p][r]
                msgs.append((i, robj,
                             [(lids[h], float(lst[b, k, h, p]),
                               float(lft[b, k, h, p]))
                              for h in range(len(lids))]))
            track = self.want_bound and not inst._is_exit[j]
            if track:
                ca = tuple(float(x) for x in ca_all[b, :P])
                cb = tuple(float(x) for x in cb_all[b, :P])
                contrib = self.crossing(p, ca, cb, self.alpha)
            else:
                ca = cb = None
                contrib = _INF
            d = (p, float(est[b, p]), float(eft[b, p]), msgs, ca, cb,
                 contrib)
            if commit:
                # keep the f64 host mirrors in lockstep via the shared
                # scalar commit (bit-equal to the device carry on the
                # f64 path; the authority for trace replay either way)
                self._commit_host(j, d[0], d[1], d[2], d[3])
            decisions.append(d)
        return decisions

    def evaluate_batch(self, js: Sequence[int]) -> List[Decision]:
        return self._run_batch(js, commit=True)

    def evaluate(self, j: int) -> Decision:
        # protocol compatibility: a single non-committing evaluation —
        # the kernel runs with is_real = 0, so the device carry passes
        # through unchanged and the caller commits via apply()
        return self._run_batch([j], commit=False)[0]

    # ----------------------------------------------- whole-schedule scan
    def _scan_tables(self) -> tuple:
        """Device-resident all-source/all-edge stacks for the scan's
        dynamic gathers (built once per backend).  The edge CTML table
        is uploaded factored by each source's distinct hop speeds and
        expanded on the device (:func:`repro_ct_expand`).  Task-indexed
        comp/ldet rows are padded to the bucketed ``Np`` (pad rows are
        never gathered — task ids are < n)."""
        if self._scan_dev is None:
            with tracing.span("repro.backend.tables"):
                inst = self.inst
                n, Np = inst.n, self._Np
                masks, valid, nhops = stacked_src_tensors(
                    inst, self._R, self._H, self._Pp, self._Lp)
                q, idx = factored_edge_ct(inst, self._R, self._H, self._Pp,
                                          self._Ep, self._np_dtype)
                comp = np.zeros((Np, self._Pp))
                comp[:n] = self._comp_rows
                ldet = np.ones((Np, self._Pp))
                ldet[:n] = self._ldet_rows
                masks, valid, nhops, q, comp, ldet = (
                    self._to_dev(x)
                    for x in (masks, valid, nhops, q, comp, ldet))
                idx = jnp.asarray(idx)
                with _x64(self._f32):
                    ct = repro_ct_expand(
                        q, idx, np.int32(len(inst._edge_index)))
                self._scan_dev = (masks, valid, nhops, ct, comp, ldet)
                if tracing.enabled():
                    tracing.count("backend.h2d_bytes", sum(
                        a.nbytes
                        for a in (masks, valid, nhops, q, idx, comp, ldet)))
                    tracing.count("backend.ct_bytes", q.nbytes + idx.nbytes)
                    tracing.count("backend.ct_columns", q.shape[2])
        return self._scan_dev

    def _scan_inputs(self, waves: Sequence[Sequence[int]]) -> tuple:
        """Stacked per-wave scan inputs (task/pred/edge ids + flags),
        bucket-padded on both the wave and slot axes; predecessors stay
        in graph order — the scan body sorts them by the carried
        ``(aft, id)`` key.  Cached per wave plan (a session re-plans the
        same queue; ``update()`` suffixes add a handful of entries)."""
        key = tuple(tuple(w) for w in waves)
        cached = self._scan_in_cache.pop(key, None)
        if cached is not None:
            self._scan_in_cache[key] = cached
            return cached
        inst = self.inst
        K, Ep = self._K, self._Ep
        Wp = bucket(len(waves))
        Bp = bucket(max(len(w) for w in waves))
        task = np.zeros((Wp, Bp), np.int32)
        real = np.zeros((Wp, Bp))
        pred = np.zeros((Wp, Bp, K), np.int32)
        pvalid = np.zeros((Wp, Bp, K))
        edge = np.full((Wp, Bp, K), Ep - 1, np.int32)
        exitf = np.zeros((Wp, Bp))
        eidx = inst._edge_index
        for wv, js in enumerate(waves):
            for b, j in enumerate(js):
                task[wv, b] = j
                real[wv, b] = 1.0
                if inst._is_exit[j]:
                    exitf[wv, b] = 1.0
                for k, i in enumerate(inst._preds[j]):
                    pred[wv, b, k] = i
                    pvalid[wv, b, k] = 1.0
                    edge[wv, b, k] = eidx[(i, j)]
        cached = (Wp, Bp, task, real, pred, pvalid, edge, exitf)
        self._scan_in_cache[key] = cached
        while len(self._scan_in_cache) > 8:
            self._scan_in_cache.popitem(last=False)
        return cached

    def _scan_dispatch(self, waves: Sequence[Sequence[int]],
                       alphas: Optional[Sequence[float]]) -> tuple:
        """Stage, launch, and fetch one whole-schedule scan: the initial
        carry comes from the f64 host mirrors (so a replayed trace
        prefix is already folded in), and the single blocking fetch
        returns every wave's winner/EST/EFT/LST/LFT/route arrays."""
        inst = self.inst
        P, Pp, L, Lp = inst.P, self._Pp, self._L, self._Lp
        n, Np = inst.n, self._Np
        consts = self._scan_tables()
        with tracing.span("repro.backend.stage"):
            Wp, Bp, task, real, pred, pvalid, edge, exitf = \
                self._scan_inputs(waves)
            dt = self._np_dtype
            lf = np.zeros(Lp)
            lf[:L] = self.link_free
            pf = np.zeros(Pp)
            pf[:P] = self.proc_free
            loads = np.zeros(Pp)
            loads[:P] = self.loads
            lop = np.zeros(Pp)
            lop[:P] = self._lop
            bp = np.ones(Pp)
            bp[:P] = self._bp
            aft0 = np.zeros(Np)
            aft0[:n] = self.aft
            # unscheduled tasks point at the pad source plane P (only
            # ever gathered through a scheduled predecessor, but a
            # negative index would wrap)
            proc0 = np.full(Np, P, np.int32)
            proc0[:n] = [p if p >= 0 else P for p in self.proc_of]
            if alphas is None:
                Ap = 0
                a_arg = np.asarray(self.alpha, dtype=dt)
            else:
                Ap = bucket(len(alphas))
                a_arg = np.asarray(
                    list(alphas) + [alphas[-1]] * (Ap - len(alphas)),
                    dtype=dt)
            run = _scan_run(Wp, Bp, self._K, self._R, self._H, Pp, Lp, Np,
                            self._Ep, Ap, self._f32)
            args = (a_arg, np.asarray(self.period, dtype=dt),
                    task, real.astype(dt), pred, pvalid.astype(dt), edge,
                    exitf.astype(dt), *consts,
                    lf.astype(dt), pf.astype(dt), loads.astype(dt),
                    lop.astype(dt), bp.astype(dt), aft0.astype(dt), proc0)
        with tracing.span("repro.backend.launch"):
            with _x64(self._f32):
                out = run(*args)
        self.n_launches += 1
        self.n_state_uploads += 1    # the initial-carry staging above
        with tracing.span("repro.backend.fetch"):
            fetched = jax.device_get(out)  # analysis: allow[host-sync] the documented one-per-SCHEDULE transfer (DESIGN.md §5); all decisions decode from this single fetch
        self.n_roundtrips += 1
        if tracing.enabled():
            tracing.count("backend.launches")
            # the host arrays staged above (the tables are already on
            # the device, counted when they were uploaded)
            tracing.count("backend.h2d_bytes",
                          sum(a.nbytes for a in args
                              if isinstance(a, np.ndarray)))
            tracing.count("backend.d2h_bytes",
                          sum(a.nbytes for a in fetched))
            # hop bodies the dispatch ran: every (padded) alpha, wave,
            # slot, predecessor, route and hop
            tracing.count("backend.hop_steps",
                          max(Ap, 1) * Wp * Bp * self._K * self._R
                          * self._H)
        return tuple(fetched)

    def _decode_scan(self, waves: Sequence[Sequence[int]], outs: tuple,
                     alpha: float, commit: bool,
                     want_bound: bool) -> List[List[Decision]]:
        """Decode one schedule's winner rows (:func:`_gather_winners`,
        one alpha) into per-wave decision lists.  The host re-derives
        each decision's sorted predecessor order from the (already
        decoded) committed AFT mirrors — f64 -> kernel-dtype casting is
        monotone, so it matches the device's ``(aft, id)`` sort on the
        f64 path exactly (and within the near-tie policy on f32)."""
        inst = self.inst
        win, est, eft, ca_all, cb_all, lst, lft, bestr = (
            x.tolist() for x in outs)
        if commit:
            aft_l, proc_l = self.aft, self.proc_of
        else:
            # one alpha of a sweep, decoded whenever it is read, so it
            # reads no run state: the sweep's queue holds every
            # predecessor, whose entry is set below before it is read
            aft_l, proc_l = [0.0] * inst.n, [-1] * inst.n
        out: List[List[Decision]] = []
        q = 0
        for js in waves:
            ds: List[Decision] = []
            for j in js:
                p = win[q]
                preds = inst._preds[j]
                if len(preds) > 1:
                    preds = sorted(preds, key=lambda i: (aft_l[i], i))
                msgs = []
                for k, i in enumerate(preds):
                    src = proc_l[i]
                    if src == p:
                        continue
                    lids, robj = inst._src_layouts[src].route_meta[p][
                        bestr[q][k]]
                    msgs.append((i, robj,
                                 [(lids[h], lst[q][k][h], lft[q][k][h])
                                  for h in range(len(lids))]))
                track = want_bound and not inst._is_exit[j]
                if track:
                    ca = tuple(ca_all[q])
                    cb = tuple(cb_all[q])
                    contrib = self.crossing(p, ca, cb, alpha)
                else:
                    ca = cb = None
                    contrib = _INF
                d: Decision = (p, est[q], eft[q], msgs, ca, cb, contrib)
                if commit:
                    # f64 host mirrors in lockstep, as on the wave path
                    self._commit_host(j, d[0], d[1], d[2], d[3])
                else:
                    # sweep decode: per-alpha locals only — the run
                    # state must stay untouched
                    proc_l[j] = p
                    aft_l[j] = d[2]
                ds.append(d)
                q += 1
            out.append(ds)
        return out

    def evaluate_plan(self, waves: Sequence[Sequence[int]],
                      timeout: Optional[float] = None,
                      bid0: int = 0) -> List[List[Decision]]:
        """One ``lax.scan`` dispatch for the whole plan (module
        docstring); falls back to the per-wave kernel loop when
        ``REPRO_PALLAS_SCAN=0``.  The watchdog compares the single
        dispatch against the aggregate budget ``timeout * len(waves)``.
        """
        if not _use_scan() or not waves:
            return super().evaluate_plan(waves, timeout=timeout,
                                         bid0=bid0)
        t0 = time.monotonic()
        outs = self._scan_dispatch(waves, None)
        if timeout is not None:
            elapsed = time.monotonic() - t0
            budget = timeout * len(waves)
            if elapsed > budget:
                raise WaveTimeoutError(bid0, elapsed, budget)
        # the per-wave device carry is now stale relative to the
        # mirrors; any later per-wave launch re-uploads first
        self._state_dirty = True
        with tracing.span("repro.backend.decode"):
            won = _gather_winners(tuple(o[None] for o in outs), waves, 1,
                                  self.inst.P)
            return self._decode_scan(waves, tuple(x[0] for x in won),
                                     self.alpha, True, self.want_bound)

    def supports_plan_sweep(self) -> bool:
        return _use_scan()

    def evaluate_plan_sweep(self, waves: Sequence[Sequence[int]],
                            alphas: Sequence[float], period: float,
                            timeout: Optional[float] = None) -> PlanSweep:
        """The (A, B) fused sweep: one ``vmap``-ed scan dispatch
        evaluates every alpha's whole schedule (module docstring).  The
        fetch is cut at once to each real decision's winner row
        (:func:`_gather_winners`), which gives every alpha's EFTs; an
        alpha's decisions are decoded only when asked for, against
        per-alpha locals — run state is never committed."""
        alphas = list(alphas)
        if not alphas or not waves:
            return PlanSweep(np.zeros((len(alphas), 0)), lambda a: [])
        t0 = time.monotonic()
        outs = self._scan_dispatch(waves, alphas)
        if timeout is not None:
            elapsed = time.monotonic() - t0
            budget = timeout * len(waves) * len(alphas)
            if elapsed > budget:
                raise WaveTimeoutError(0, elapsed, budget)
        with tracing.span("repro.backend.decode"):
            won = _gather_winners(outs, waves, len(alphas), self.inst.P)

        def decode(a: int) -> List[List[Decision]]:
            tracing.count("backend.alphas_decoded")
            with tracing.span("repro.backend.decode"):
                return self._decode_scan(waves, tuple(x[a] for x in won),
                                         alphas[a], False, True)
        return PlanSweep(won[2], decode)
