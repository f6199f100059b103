"""The device path compiles for a TPU v5e chip — checked here without one.

The Pallas interpreter never checks Mosaic's tiling rules or the chip's
memory, so each test builds a real workload under the compiled-path
numerics (float32, tile-padded shapes), captures the exact arguments the
backend would dispatch, and compiles the jitted runner for one chip of a
*described* ``v5e:2x2`` topology.  Nothing runs, so these tests say
nothing about results or time; they catch what the chip's compiler would
refuse before any chip time is spent.

Shapes:

* the whole-schedule ``lax.scan`` at exp7's largest cell (P=16 fully
  switched, n=500), without (A=0) and with the fused 101-alpha sweep
  axis (bucketed to A=128);
* the scan at exp6's multi-hop placement cell (qwen3-8b pipeline on
  ``tpu_slice_topology(8, 32, pods=2)``) with its 61-alpha sweep;
* the per-wave ``pallas_call`` kernel at a P=16 wave;
* the fused 101-alpha sweep at the ``dbrx-ep16-mesh4x4-prefill`` cell's
  padded shape: a 960-task expert-parallel step of dbrx-132b on the 4x4
  ICI mesh (in-degree 16, two routes of up to 6 hops a pair), and the
  device expansion of its factored edge CTML table.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and test collection must not
depend on whether it can.
"""
import os

import numpy as np
import pytest

pytest.importorskip("jax")

import jax  # noqa: E402

import repro.core.backends.pallas as pb  # noqa: E402
from repro.core import (CompiledInstance, HVLB_CC_B,  # noqa: E402
                        fully_switched_topology, random_spg)
from repro.core.ranks import hprv_b, priority_queue, rank_matrix  # noqa: E402


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 slice, with the persistent
    compilation cache off (a described-chip entry cannot be read back
    without the chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()
    mp.undo()


@pytest.fixture
def chip_numerics(monkeypatch):
    """The compiled path's numerics under the interpreter: float32 and
    tile-padded (P to 8 sublanes, L to 128 lanes)."""
    monkeypatch.setenv("REPRO_PALLAS_DTYPE", "float32")
    monkeypatch.setenv("REPRO_PALLAS_TILE", "1")


class _Captured(Exception):
    pass


def _capture(monkeypatch, factory: str, drive):
    """Run ``drive()`` until the backend asks ``factory`` for a runner
    and calls it; return (runner key, call arguments)."""
    seen = {}

    def fake(*key):
        def run(*args):
            seen["key"], seen["args"] = key, args
            raise _Captured
        return run

    with monkeypatch.context() as m:
        m.setattr(pb, factory, fake)
        with pytest.raises(_Captured):
            drive()
    return seen["key"], seen["args"]


def _compile(one_chip, runner, args):
    def spec(x):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    return runner.lower(*jax.tree.map(spec, args)).compile()


def _exp7_instance(P: int = 16, n: int = 500):
    """exp7's (P, n) cell: its topology and graph seeds."""
    rng = np.random.default_rng(77)
    tg = fully_switched_topology(P, rates=rng.uniform(0.6, 1.2, size=P),
                                 link_speeds=rng.uniform(0.5, 3.0, size=P))
    g = random_spg(n, np.random.default_rng(7000 + n + P), ccr=1.0, tg=tg,
                   max_in=3, max_out=6)
    r = rank_matrix(g, tg)
    return CompiledInstance(g, tg, rank=r), priority_queue(
        hprv_b(g, tg, r), r.mean(1))


SWEEP = HVLB_CC_B(alpha_max=5.0, alpha_step=0.05)


@pytest.mark.parametrize("n_alphas", [0, 101], ids=["A0", "A128"])
def test_scan_compiles_exp7_p16_n500(one_chip, chip_numerics, monkeypatch,
                                     n_alphas):
    inst, q = _exp7_instance()
    if n_alphas:
        alphas = [k * SWEEP.alpha_step for k in range(n_alphas)]
        drive = lambda: inst.schedule_sweep(q, alphas, backend="pallas")  # noqa: E731
    else:
        drive = lambda: inst.schedule(q, alpha=1.0, backend="pallas")  # noqa: E731
    key, args = _capture(monkeypatch, "_scan_run", drive)
    W, B, K, R, H, Pp, Lp, Np, Ep, A, f32 = key
    assert f32 and (Pp, Lp, Np) == (16, 128, 512)
    assert A == (128 if n_alphas else 0)
    compiled = _compile(one_chip, pb._scan_run(*key), args)
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_scan_compiles_exp6_multihop_placement(one_chip, chip_numerics,
                                               monkeypatch):
    from repro.configs import ARCHS, SHAPES
    from repro.planner import pipeline_graph, tpu_slice_topology

    g = pipeline_graph(ARCHS["qwen3-8b"], SHAPES["train_4k"],
                       n_microbatches=8)
    tg = tpu_slice_topology(n_slices=8, chips_per_slice=32, pods=2)
    r = rank_matrix(g, tg)
    inst = CompiledInstance(g, tg, rank=r)
    q = priority_queue(hprv_b(g, tg, r), r.mean(1))
    alphas = [k * 0.05 for k in range(61)]        # plan_placement's grid
    key, args = _capture(
        monkeypatch, "_scan_run",
        lambda: inst.schedule_sweep(q, alphas, backend="pallas"))
    H, A = key[4], key[9]
    assert H > 2 and A == 64                     # multi-hop routes, swept
    _compile(one_chip, pb._scan_run(*key), args)


def test_per_wave_kernel_compiles_p16(one_chip, chip_numerics, monkeypatch):
    inst, q = _exp7_instance()
    monkeypatch.setenv("REPRO_PALLAS_SCAN", "0")
    key, args = _capture(
        monkeypatch, "_compiled_run",
        lambda: inst.schedule(q, alpha=1.0, backend="pallas"))
    B, K, R, H, P, L, f32, _interpret = key
    assert f32 and (P, L) == (16, 128)
    runner = pb._compiled_run(B, K, R, H, P, L, True, False)
    compiled = _compile(one_chip, runner, args)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def dbrx_ep16():
    """15 layers x 2 microbatches x (16 attention + 16 expert tasks) of
    dbrx-132b on the 4x4 mesh, and its HVLB_CC(B) queue."""
    from repro.configs import ARCHS
    from repro.planner import ep_step_graph, tpu_mesh_topology

    tokens = np.full((15, 2, 16, 16), 512)        # 2048 tokens x top-4
    kv = 2048 * (1 + np.arange(32).reshape(2, 16) % 16)
    tg = tpu_mesh_topology(4, 4, degraded={5: 0.8})
    g = ep_step_graph(ARCHS["dbrx-132b"], tokens, kv, 2, tg)
    r = rank_matrix(g, tg)
    return CompiledInstance(g, tg, rank=r), priority_queue(
        hprv_b(g, tg, r), r.mean(1))


def test_scan_compiles_dbrx_ep16_mesh4x4(one_chip, chip_numerics,
                                          monkeypatch, dbrx_ep16):
    """(W, B, K, R, H, Pp, Lp, Np, Ep, A) = (64, 16, 16, 2, 6, 16, 128,
    1024, 16384, 128), within the chip's 16 GB."""
    inst, q = dbrx_ep16
    alphas = [k * 0.05 for k in range(101)]       # plan_placement's grid
    key, args = _capture(
        monkeypatch, "_scan_run",
        lambda: inst.schedule_sweep(q, alphas, backend="pallas"))
    assert key == (64, 16, 16, 2, 6, 16, 128, 1024, 16384, 128, True)
    mem = _compile(one_chip, pb._scan_run(*key), args).memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9


def test_ct_expand_compiles_dbrx_ep16_mesh4x4(one_chip, chip_numerics,
                                              dbrx_ep16):
    """The edge CTML table's expansion at the same cell: one quotient
    column (every ICI link at one speed) up, the (Ep, P + 1, R, H, Pp)
    float32 table out."""
    from repro.core.backends.layout import factored_edge_ct

    inst, _ = dbrx_ep16
    be = pb.PallasBackend(inst)
    q, idx = factored_edge_ct(inst, be._R, be._H, be._Pp, be._Ep,
                              np.float32)
    assert q.shape == (16384, 17, 1) and idx.shape == (17, 2, 6, 16)
    mem = _compile(one_chip, pb.repro_ct_expand,
                   (q, idx, np.int32(len(inst._edge_index)))
                   ).memory_analysis()
    assert mem.output_size_in_bytes == 16384 * 17 * 2 * 6 * 16 * 4
    assert mem.argument_size_in_bytes < 2e6
