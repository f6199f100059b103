"""Shared route-tensor layout precompute for the array backends.

The batched backends (NumPy :class:`~.vector.VectorBackend`, Pallas
:class:`~.pallas.PallasBackend`) evaluate all P placement candidates of
one dequeued task at once, which requires the topology's route tables in
tensor form: per hop, a ``(P,)`` row of link ids / gather indices /
speeds per destination lane.  Those tensors are a pure function of
``(topology, source processor)`` — the message *edge* only contributes a
scalar volume ``tpl(e_ij | src)`` that scales the per-hop CTML row — so
they are built **once per (instance, src)** here and shared by

  * every edge whose source task sits on ``src`` (the vector backend
    used to rebuild them per ``(edge, src)``, which made a cold submit
    cost ~2x a warm pass at n = 500 — the per-edge work is now one
    vectorized CTML fill over the shared layout), and
  * every backend bound to the same :class:`~..engine.CompiledInstance`
    (the cache lives on the instance, not the backend).

Bit-exactness: :func:`edge_ct` performs the same IEEE-754 operations as
the scalar ``CompiledInstance.msg_plans_for`` path — one ``tpl / speed``
division per hop plus the Eq. 15 quantization (``round`` is IEEE
round-half-even in both ``float(round(t))`` and ``np.rint``; ``ceil``
likewise) — elementwise over the layout tensors, so the produced CTML
floats equal the scalar plan cache's bit for bit
(``tests/test_backend_equivalence.py`` holds all backends to it).
"""
from __future__ import annotations

from typing import List, Optional, Tuple, TYPE_CHECKING

import numpy as np

if TYPE_CHECKING:                                   # pragma: no cover
    from ..engine import CompiledInstance

__all__ = ["CT_COL0", "CT_NEG", "CT_ZERO", "LANE", "SUBLANE_F32",
           "SrcLayout", "bucket", "edge_ct", "ensure_ct_table",
           "factored_edge_ct", "pad_dim", "padded_edge_ct",
           "padded_src_tensors", "src_layout", "stacked_src_tensors"]

_NEG_INF = float("-inf")


class SrcLayout:
    """Padded route tensors of one source processor against a topology.

    Hop tensors are ``(P, R, H)`` — destination lane x route x hop —
    where ``R``/``H`` are the maximum route count / hop count over all
    destinations for this source.  Padding conventions (shared contract
    of every array backend):

      * hop padding (``pad``): no real link — reads must see ``-inf``
        and the CTML must be ``-inf`` so both Eq. 13/14 running maxima
        are no-ops;
      * route padding (``invalid``): masked to ``+inf`` arrival so it
        never wins the (LFT, hops, index) route selection;
      * the ``src`` destination lane owns a fake zero-CTML route 0 whose
        final LFT is exactly ``aft_i`` — the scalar path's
        same-processor arrival contribution — so no post-hoc masking.

    ``read_idx``/``base_idx``/``write_idx`` (and the contiguous
    ``av_idx``/``base_flat``/``w_rows`` forms used by the vector
    backend's single-route fast path) address the vector backend's flat
    ``(P*L + 2,)`` lane buffer: slot ``P*L`` is the write-only sink,
    slot ``P*L + 1`` the read-only ``-inf``; the committed ``(L + 1,)``
    link state uses slot ``L`` as its ``-inf``.
    """

    __slots__ = ("src", "P", "L", "R", "H", "lid", "spd", "pad",
                 "nhops", "invalid", "has_invalid", "route_meta",
                 "read_idx", "base_idx", "write_idx",
                 "av_idx", "base_flat", "w_rows",
                 "spd_rows", "pad_flat", "ct_table")

    def __init__(self, inst: "CompiledInstance", src: int) -> None:
        P = inst.P
        L = inst._n_links
        self.src, self.P, self.L = src, P, L
        routes = inst._routes
        R = H = 1
        route_meta: List[List[Tuple[Tuple[int, ...], Tuple[str, ...]]]] = []
        for dst in range(P):
            if dst == src:
                route_meta.append([])
                continue
            rr = routes[(src, dst)]
            meta = []
            for (lids, _spds, robj) in rr:
                meta.append((lids, robj))
                H = max(H, len(lids))
            R = max(R, len(rr))
            route_meta.append(meta)
        self.R, self.H = R, H
        self.route_meta = route_meta

        sink = P * L
        neg = P * L + 1
        lid = np.full((P, R, H), L, dtype=np.intp)      # L = virtual pad link
        spd = np.ones((P, R, H), dtype=np.float64)
        pad = np.ones((P, R, H), dtype=bool)
        read_idx = np.full((P, R, H), neg, dtype=np.intp)
        base_idx = np.full((P, R, H), L, dtype=np.intp)  # L = -inf slot
        write_idx = np.full((P, R, H), sink, dtype=np.intp)
        nhops = np.zeros((P, R), dtype=np.int64)
        invalid = np.ones((P, R), dtype=bool)
        for dst in range(P):
            if dst == src:
                invalid[dst, 0] = False      # fake zero-CTML route
                continue
            for r, (lids, spds, _robj) in enumerate(routes[(src, dst)]):
                invalid[dst, r] = False
                nhops[dst, r] = len(lids)
                for h, l in enumerate(lids):
                    lid[dst, r, h] = l
                    spd[dst, r, h] = spds[h]
                    pad[dst, r, h] = False
                    read_idx[dst, r, h] = dst * L + l
                    base_idx[dst, r, h] = l
                    write_idx[dst, r, h] = dst * L + l
        self.lid, self.spd, self.pad = lid, spd, pad
        self.nhops, self.invalid = nhops, invalid
        self.has_invalid = bool(invalid.any())
        self.read_idx, self.base_idx, self.write_idx = (read_idx, base_idx,
                                                        write_idx)
        # contiguous single-route forms (hop-major) for the R == 1 path
        self.av_idx = np.ascontiguousarray(read_idx[:, 0, :].T).ravel()
        self.base_flat = np.ascontiguousarray(base_idx[:, 0, :].T).ravel()
        self.w_rows = [np.ascontiguousarray(write_idx[:, 0, h])
                       for h in range(H)]
        # per-edge CTML fill helpers (edge_ct): hop-major speeds for the
        # single-route path, flat pad indices for either shape
        self.spd_rows: Optional[np.ndarray]
        if R == 1:
            self.spd_rows = np.ascontiguousarray(spd[:, 0, :].T)  # (H, P)
            self.pad_flat = np.flatnonzero(pad[:, 0, :].T.ravel())
        else:
            self.spd_rows = None
            self.pad_flat = np.flatnonzero(pad.ravel())
        # all-edge CTML table, built lazily
        self.ct_table: Optional[np.ndarray] = None


def src_layout(inst: "CompiledInstance", src: int) -> SrcLayout:
    """The (cached) :class:`SrcLayout` of ``src`` for one instance.

    The cache lives on the :class:`~..engine.CompiledInstance`
    (``inst._src_layouts``) so every backend bound to the instance —
    and every edge — shares one build.
    """
    lay = inst._src_layouts.get(src)
    if lay is None:
        lay = SrcLayout(inst, src)
        inst._src_layouts[src] = lay
    return lay


def ensure_ct_table(inst: "CompiledInstance", lay: SrcLayout) -> np.ndarray:
    """Eq. 15 CTML tensors of *every* edge from ``lay.src``, in one shot.

    Route-tensor precompilation: the first decision that places a task
    on ``src`` pays one vectorized ``(E, ...)`` division + quantization
    over all E graph edges, and every later edge evaluated from ``src``
    is a table row view — so a cold submit does per-*src* work (P of
    them), not per-(edge, src) work (O(E * P) of them).

    Identical floats to the scalar ``msg_plans_for`` path: ``tpl /
    speed`` is one IEEE division either way, ``np.rint``/``np.ceil``
    match ``float(round(t))`` / ``float(np.ceil(t))`` elementwise.

    Row shape follows the backend fast paths: hop-major ``(H, P)`` for
    single-route layouts, the full ``(P, R, H)`` tensor otherwise.
    ~``E * P * R * H`` doubles per source processor — a few MB at the
    exp7 n=500 scale.
    """
    t = inst._tpl_matrix[:, lay.src]                         # (E,)
    single = lay.R == 1
    if single:
        ct = t[:, None, None] / lay.spd_rows                 # (E, H, P)
    else:
        ct = t[:, None, None, None] / lay.spd                # (E, P, R, H)
    mode = inst._ctml_mode
    if mode == "round":
        np.rint(ct, out=ct)
    elif mode == "ceil":
        np.ceil(ct, out=ct)
    ct.reshape(len(t), -1)[:, lay.pad_flat] = _NEG_INF
    if single:
        ct[:, :, lay.src] = 0.0      # fake route: final LFT == aft_i
    else:
        ct[:, lay.src, 0, :] = 0.0
    lay.ct_table = ct
    return ct


def edge_ct(inst: "CompiledInstance", lay: SrcLayout,
            i: int, j: int) -> np.ndarray:
    """CTML tensor of edge ``e_ij`` from ``lay.src`` — a row view of the
    precompiled all-edge table (see :func:`ensure_ct_table`)."""
    tab = lay.ct_table
    if tab is None:
        tab = ensure_ct_table(inst, lay)
    return tab[inst._edge_index[(i, j)]]


# ----------------------------------------------------------------------
# Tile-padded variants for the device backend
# ----------------------------------------------------------------------
# TPU vector registers are (sublane, lane) tiles; for float32 the minimum
# tile is (8, 128).  The Pallas backend's dominant 2-D arrays put the
# candidate-processor axis on sublanes and the link axis on lanes (the
# (P, L) lane buffer and the per-hop one-hot masks), so a Mosaic-compiled
# kernel wants P padded to a sublane multiple and L to a lane multiple.
# Padding is arithmetic, not control flow (same contract as the hop/route
# padding above): padded processor lanes carry +inf computation cost and
# all-invalid routes, so they never win a selection and never commit;
# padded links are never masked in, so they are never read or written.
LANE = 128          # last-dim tile multiple (all dtypes)
SUBLANE_F32 = 8     # second-to-last-dim tile multiple for float32


def pad_dim(x: int, multiple: int) -> int:
    """``x`` rounded up to a multiple (identity when ``multiple`` is 1)."""
    return -(-x // multiple) * multiple


def padded_src_tensors(inst: "CompiledInstance", src: int, R: int, H: int,
                       Pp: int, Lp: int) -> Tuple[np.ndarray, np.ndarray,
                                                  np.ndarray]:
    """Route tensors of ``src`` padded to instance-global device dims.

    Returns ``(masks, valid, nhops)`` as float64 NumPy arrays (the device
    backend casts to its kernel dtype on upload):

      * ``masks``  — ``(R, H, Pp, Lp)`` one-hot hop masks over the link
        axis (zero rows for hop/route/lane padding and for the
        ``dst == src`` fake route, which owns no links),
      * ``valid``  — ``(R, Pp)`` route validity (0 for route padding and
        for every tile-padded processor lane),
      * ``nhops``  — ``(R, Pp)`` per-route hop counts.

    ``R``/``H`` are the instance-global maxima over all sources (so one
    compiled kernel serves every decision); ``Pp``/``Lp`` are the
    processor/link counts, tile-padded via :func:`pad_dim` when the
    backend targets a real Mosaic compile.
    """
    lay = src_layout(inst, src)
    P = lay.P
    masks = np.zeros((R, H, Pp, Lp))
    for dst in range(P):
        for r in range(lay.R):
            for h in range(int(lay.nhops[dst, r])):
                masks[r, h, dst, lay.lid[dst, r, h]] = 1.0
    valid = np.zeros((R, Pp))
    valid[:lay.R, :P] = (~lay.invalid).T
    nhops = np.zeros((R, Pp))
    nhops[:lay.R, :P] = lay.nhops.T
    return masks, valid, nhops


def stacked_src_tensors(inst: "CompiledInstance", R: int, H: int,
                        Pp: int, Lp: int) -> Tuple[np.ndarray, np.ndarray,
                                                   np.ndarray]:
    """Route tensors of **every** source processor, stacked on a leading
    src axis, for the device-resident scan path (DESIGN.md §5).

    The whole-schedule ``lax.scan`` cannot stage per-predecessor tensors
    on the host (a predecessor's placement is decided *inside* the scan),
    so the backend uploads the full ``(P + 1, ...)`` stack once and the
    scan body gathers row ``proc_of[pred]`` dynamically.  Row ``P`` is
    the **padding predecessor** plane — all-zero masks, one valid
    zero-hop route per lane — mirroring the per-wave path's pad tensors:
    a padded slot's arrival/commit contributions drop out of the exact
    max algebra.

    Returns ``(masks, valid, nhops)`` shaped ``(P + 1, R, H, Pp, Lp)`` /
    ``(P + 1, R, Pp)`` / ``(P + 1, R, Pp)`` (float64; the backend casts
    on upload).
    """
    P = inst.P
    masks = np.zeros((P + 1, R, H, Pp, Lp))
    valid = np.zeros((P + 1, R, Pp))
    nhops = np.zeros((P + 1, R, Pp))
    for s in range(P):
        masks[s], valid[s], nhops[s] = padded_src_tensors(
            inst, s, R, H, Pp, Lp)
    valid[P, 0, :] = 1.0             # pad src: fake zero-hop route 0
    return masks, valid, nhops


# codes of the factored CTML table's index (:func:`factored_edge_ct`):
# ``CT_NEG`` reads -inf (padding), ``CT_ZERO`` reads 0.0 (the src lane's
# fake route 0), and ``CT_COL0 + u`` reads quotient column ``u``
CT_NEG, CT_ZERO, CT_COL0 = 0, 1, 2


def bucket(b: int) -> int:
    """Smallest power of two >= b (bounds compiled kernel variants)."""
    n = 1
    while n < b:
        n *= 2
    return n


def factored_edge_ct(inst: "CompiledInstance", R: int, H: int, Pp: int,
                     Ep: int, dtype) -> Tuple[np.ndarray, np.ndarray]:
    """Eq. 15 CTML of **every** edge from **every** source, factored by
    each source's distinct hop speeds, for the scan path's
    ``(Ep, P + 1, R, H, Pp)`` table (expanded on the device by
    ``pallas.repro_ct_expand``).

    The CTML of edge ``e`` from ``s`` at ``(r, h)`` toward lane ``p`` is
    ``quant(tpl[e, s] / spd[s, r, h, p])``, and a source's hops share a
    few speeds, so the table holds one quotient per (edge, source,
    distinct speed).  Returns

      * ``q`` — ``(Ep, P + 1, Uc)`` in ``dtype``: ``q[e, s, u]`` is edge
        ``e``'s quotient at the ``u``-th smallest speed of ``s``; the same
        float64 division and ``np.rint``/``np.ceil`` as
        :func:`ensure_ct_table`, then the cast, so each float is the one
        the per-wave path's :func:`padded_edge_ct` gives.  Edge rows
        ``>= E``, source plane ``P`` and unused columns are ``-inf``;
      * ``idx`` — ``(P + 1, R, H, Pp)`` int32 codes (``CT_NEG``,
        ``CT_ZERO``, ``CT_COL0 + u``): hop/route/lane padding and the
        pad source plane read ``-inf``, the src lane's fake route 0 reads
        0.0 (``-inf`` on the pad edge rows, as the table had it).

    ``Uc`` is the largest count of distinct speeds of any source,
    bucketed (:func:`bucket`) but never above the ``R * H * Pp``
    positions a source has, so ``q`` is never larger than the table.
    """
    P = inst.P
    E = len(inst._edge_index)
    assert Ep >= E + 1
    lays = [src_layout(inst, s) for s in range(P)]
    speeds = [np.unique(lay.spd[~lay.pad]) for lay in lays]
    Uc = min(bucket(max([1] + [len(u) for u in speeds])), R * H * Pp)
    q = np.full((Ep, P + 1, Uc), _NEG_INF)
    idx = np.full((P + 1, R, H, Pp), CT_NEG, dtype=np.int32)
    mode = inst._ctml_mode
    for s, (lay, u) in enumerate(zip(lays, speeds)):
        ct = inst._tpl_matrix[:, s, None] / u                # (E, U_s)
        if mode == "round":
            np.rint(ct, out=ct)
        elif mode == "ceil":
            np.ceil(ct, out=ct)
        q[:E, s, :len(u)] = ct
        code = CT_COL0 + np.searchsorted(u, lay.spd)         # (P, R, H)
        code[lay.pad] = CT_NEG
        code[s, 0, :] = CT_ZERO      # fake route: final LFT == aft_i
        idx[s, :lay.R, :lay.H, :lay.P] = code.transpose(1, 2, 0)
    return q.astype(dtype), idx


def padded_edge_ct(inst: "CompiledInstance", lay: SrcLayout, i: int, j: int,
                   R: int, H: int, Pp: int) -> np.ndarray:
    """CTML tensor of edge ``e_ij`` from ``lay.src`` padded to the
    instance-global ``(R, H, Pp)`` device shape: hop/route/lane padding
    reads ``-inf`` (a no-op of the Eq. 13-14 max algebra; padded lanes
    are additionally masked invalid in :func:`padded_src_tensors`)."""
    row = edge_ct(inst, lay, i, j)
    full = np.full((R, H, Pp), _NEG_INF)
    if lay.R == 1:
        full[0, :lay.H, :lay.P] = row                    # (H, P) hop-major
    else:
        full[:lay.R, :lay.H, :lay.P] = row.transpose(1, 2, 0)  # (P, R, H)
    return full
