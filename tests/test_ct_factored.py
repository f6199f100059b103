"""The scan path's edge CTML table, uploaded factored by each source's
distinct hop speeds (``layout.factored_edge_ct``) and expanded on the
device (``pallas.repro_ct_expand``), is bit for bit the stack of the
per-wave path's ``padded_edge_ct`` over every edge and source, with the
pad edge rows and the pad source plane at ``-inf``."""
import numpy as np
import pytest

pytest.importorskip("jax")

from repro.core import CompiledInstance, random_spg  # noqa: E402
from repro.core.backends.layout import (  # noqa: E402
    bucket, factored_edge_ct, padded_edge_ct, src_layout)
from repro.core.backends.pallas import PallasBackend  # noqa: E402
from repro.core.faults import FaultSpec, LinkDown  # noqa: E402
from repro.core.topology import (Topology,  # noqa: E402
                                 fully_switched_topology)
from repro.planner import tpu_mesh_topology  # noqa: E402

# the submit cell's switch: the paper's Fig. 2 speeds over 16 links
SWITCH_SPEEDS = [1.0, 1.0, 3.0, 1.0] * 4
SWITCH_RATES = [1.0, 0.67, 0.83, 1.0] * 4


def _mode(tg: Topology, mode: str) -> Topology:
    return Topology(list(tg.proc_names), tg.rates.copy(),
                    dict(tg.link_speed),
                    {k: list(v) for k, v in tg.routes.items()},
                    ctml_mode=mode)


def _distinct() -> Topology:
    """Six processors, one direct link per pair, every link at its own
    speed: each source's hops all run at distinct speeds."""
    P = 6
    links, routes = {}, {}
    for a in range(P):
        for b in range(a + 1, P):
            name = f"l{a}{b}"
            links[name] = 0.5 + len(links) * 0.37
            routes[(a, b)] = [(name,)]
    return Topology([f"p{i}" for i in range(P)], np.linspace(0.6, 1.1, P),
                    links, routes)


# (topology, faults, distinct speeds of the source with the most)
CASES = {
    "mesh2x2": (lambda: tpu_mesh_topology(2, 2), None, 1),
    "mesh4x4": (lambda: tpu_mesh_topology(4, 4), None, 1),
    "switch16": (lambda: fully_switched_topology(16, SWITCH_RATES,
                                                 SWITCH_SPEEDS), None, 2),
    "mesh2x2-link-down": (lambda: tpu_mesh_topology(2, 2), "x0.0", 2),
    "distinct": (_distinct, None, 5),
}


def _instance(case: str, mode: str):
    build, down, columns = CASES[case]
    tg = _mode(build(), mode)
    g = random_spg(24, np.random.default_rng(3), ccr=1.0, tg=tg,
                   outdeg_constraint=True)
    # volumes of a few time units on the slowest link, so that round and
    # ceil keep fractions to cut
    scale = 5.3 * min(tg.link_speed.values()) / np.mean(list(g.tpl.values()))
    g.tpl = {e: v * scale for e, v in g.tpl.items()}
    faults = (FaultSpec.from_faults([LinkDown(down)], tg)
              if down else None)
    return CompiledInstance(g, tg, faults=faults), columns


def _oracle(inst, be, dtype) -> np.ndarray:
    R, H, Pp, Ep = be._R, be._H, be._Pp, be._Ep
    full = np.full((Ep, inst.P + 1, R, H, Pp), -np.inf)
    for (i, j), e in inst._edge_index.items():
        for s in range(inst.P):
            full[e, s] = padded_edge_ct(inst, src_layout(inst, s), i, j,
                                        R, H, Pp)
    return full.astype(dtype)


@pytest.mark.parametrize("dtype,tile", [("float64", "0"), ("float32", "1")])
@pytest.mark.parametrize("mode", ["exact", "round", "ceil"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_expanded_table_is_the_stacked_table(case, mode, dtype, tile,
                                             monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_DTYPE", dtype)
    monkeypatch.setenv("REPRO_PALLAS_TILE", tile)
    inst, columns = _instance(case, mode)
    be = PallasBackend(inst)
    got = np.asarray(be._scan_tables()[3])
    want = _oracle(inst, be, np.dtype(dtype))
    assert got.dtype == want.dtype and got.shape == want.shape
    # bits, so -0.0 against 0.0 or a last-place difference shows
    uint = np.uint64 if dtype == "float64" else np.uint32
    np.testing.assert_array_equal(got.view(uint), want.view(uint))
    assert np.isneginf(got[len(inst._edge_index):]).all()
    assert np.isneginf(got[:, inst.P]).all()
    # the factored upload: one quotient column per distinct speed,
    # bucketed, and never more bytes than the table it stands for
    q, idx = factored_edge_ct(inst, be._R, be._H, be._Pp, be._Ep,
                              np.dtype(dtype))
    assert q.shape == (be._Ep, inst.P + 1,
                       min(bucket(columns), be._R * be._H * be._Pp))
    assert q.nbytes <= want.nbytes
    assert idx.dtype == np.int32 and idx.shape == want.shape[1:]
