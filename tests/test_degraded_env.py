"""Degraded-environment behaviour: a broken/missing/hung device backend
demotes down the fallback chain (pallas -> vector -> scalar) instead of
failing the session, lands on a bit-identical plan (decisions are
backend-invariant), records the demotion on ``Plan.fallback`` and warns
once per process."""
import numpy as np
import pytest

import repro.core.api as api_mod
import repro.core.backends as backends_mod
from repro.core import (HVLB_CC_B, Scheduler, WaveTimeoutError,
                        paper_topology, random_spg)


def _case(seed=0, n=20):
    rng = np.random.default_rng(seed)
    tg = paper_topology()
    g = random_spg(n, rng, ccr=1.0, tg=tg, outdeg_constraint=True)
    return tg, g


def _pol():
    return HVLB_CC_B(alpha_max=1.0, alpha_step=0.5)


def _scalar_reference(tg, g):
    return Scheduler(tg, policy=_pol(), backend="scalar").submit(g)


def _assert_same_decisions(plan, ref):
    assert np.array_equal(plan.schedule.proc, ref.schedule.proc)
    assert np.array_equal(plan.schedule.start, ref.schedule.start)
    assert np.array_equal(plan.schedule.finish, ref.schedule.finish)


def test_pallas_without_jax_demotes_at_resolve_time(monkeypatch):
    """backend='pallas' on a jax-free install must not kill the session:
    the request demotes to the NumPy chain with a recorded reason."""
    tg, g = _case()
    monkeypatch.setattr(backends_mod, "_pallas_available", lambda: False)
    monkeypatch.delitem(backends_mod.BACKENDS, "pallas", raising=False)
    monkeypatch.setattr(api_mod, "_FALLBACK_WARNED", set())
    sched = Scheduler(tg, policy=_pol(), backend="pallas")
    with pytest.warns(RuntimeWarning, match="pallas"):
        plan = sched.submit(g)
    assert plan.fallback is not None and len(plan.fallback) == 1
    src, dst, reason = plan.fallback[0]
    assert src == "pallas" and dst in ("vector", "scalar")
    assert "jax" in reason
    assert plan.backend == dst
    _assert_same_decisions(plan, _scalar_reference(tg, g))


def test_pallas_kernel_failure_demotes_at_plan_time(monkeypatch):
    """Per-wave path: an injected ``evaluate_batch`` fault demotes the
    plan (scan disabled so the wave kernel actually runs)."""
    pytest.importorskip("jax")
    from repro.core.backends.pallas import PallasBackend

    def _boom(self, js):
        raise RuntimeError("injected kernel failure")

    monkeypatch.setenv("REPRO_PALLAS_SCAN", "0")
    monkeypatch.setattr(PallasBackend, "evaluate_batch", _boom)
    monkeypatch.setattr(api_mod, "_FALLBACK_WARNED", set())
    tg, g = _case()
    sched = Scheduler(tg, policy=_pol(), backend="pallas")
    with pytest.warns(RuntimeWarning, match="injected kernel failure"):
        plan = sched.submit(g)
    assert plan.fallback is not None
    assert plan.fallback[0][0] == "pallas"
    assert "injected kernel failure" in plan.fallback[0][2]
    assert plan.backend in ("vector", "scalar")
    _assert_same_decisions(plan, _scalar_reference(tg, g))


def test_pallas_scan_failure_demotes_at_plan_time(monkeypatch):
    """Scan path: a fault inside the whole-schedule dispatch demotes the
    plan exactly like a per-wave kernel fault."""
    pytest.importorskip("jax")
    from repro.core.backends.pallas import PallasBackend

    def _boom(self, waves, alphas):
        raise RuntimeError("injected scan failure")

    monkeypatch.setattr(PallasBackend, "_scan_dispatch", _boom)
    monkeypatch.setattr(api_mod, "_FALLBACK_WARNED", set())
    tg, g = _case()
    sched = Scheduler(tg, policy=_pol(), backend="pallas")
    with pytest.warns(RuntimeWarning, match="injected scan failure"):
        plan = sched.submit(g)
    assert plan.fallback is not None
    assert plan.fallback[0][0] == "pallas"
    assert "injected scan failure" in plan.fallback[0][2]
    assert plan.backend in ("vector", "scalar")
    _assert_same_decisions(plan, _scalar_reference(tg, g))


def test_wave_timeout_demotes_device_backend(monkeypatch):
    """An (effectively) hung pallas wave trips the watchdog and demotes;
    the NumPy backends never run under the watchdog."""
    pytest.importorskip("jax")
    monkeypatch.setattr(api_mod, "_FALLBACK_WARNED", set())
    tg, g = _case()
    sched = Scheduler(tg, policy=_pol(), backend="pallas",
                      wave_timeout=1e-9)
    with pytest.warns(RuntimeWarning, match="WaveTimeoutError"):
        plan = sched.submit(g)
    assert plan.fallback is not None
    assert plan.fallback[0][0] == "pallas"
    assert plan.backend in ("vector", "scalar")
    _assert_same_decisions(plan, _scalar_reference(tg, g))


def test_wave_timeout_ignored_by_numpy_backends():
    tg, g = _case()
    sched = Scheduler(tg, policy=_pol(), backend="scalar",
                      wave_timeout=1e-9)
    plan = sched.submit(g)                  # no watchdog, no demotion
    assert plan.fallback is None
    _assert_same_decisions(plan, _scalar_reference(tg, g))


def test_wave_timeout_error_shape():
    e = WaveTimeoutError(3, 0.5, 0.1)
    assert e.wave == 3 and "watchdog" in str(e)


def test_nondevice_backend_errors_are_not_swallowed():
    """Only device backends demote: an unknown explicit backend raises."""
    tg, g = _case()
    sched = Scheduler(tg, policy=_pol())
    with pytest.raises(ValueError, match="unknown backend"):
        sched.submit(g, backend="gpu3000")


def test_fallback_warns_only_once(monkeypatch):
    monkeypatch.setattr(backends_mod, "_pallas_available", lambda: False)
    monkeypatch.delitem(backends_mod.BACKENDS, "pallas", raising=False)
    monkeypatch.setattr(api_mod, "_FALLBACK_WARNED", set())
    tg, g = _case()
    sched = Scheduler(tg, policy=_pol(), backend="pallas")
    with pytest.warns(RuntimeWarning):
        sched.submit(g)
    _, g2 = _case(seed=1)
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")            # a second warn would raise
        plan = sched.submit(g2)
    assert plan.fallback is not None        # still recorded on the plan


def test_broken_device_sweep_is_not_silently_hosted(monkeypatch):
    """A device backend that fails to build raises from
    ``sweep_supported`` instead of quietly picking the host per-alpha
    loop; at the session level that failure takes the visible fallback
    chain (recorded on ``Plan.fallback``, warned)."""
    pytest.importorskip("jax")
    from repro.core import CompiledInstance
    from repro.core.backends.pallas import PallasBackend

    def _boom(self, inst):
        raise RuntimeError("injected backend build failure")

    monkeypatch.setattr(PallasBackend, "__init__", _boom)
    monkeypatch.setattr(api_mod, "_FALLBACK_WARNED", set())
    tg, g = _case()
    with pytest.raises(RuntimeError, match="injected backend build"):
        CompiledInstance(g, tg).sweep_supported("pallas")
    assert CompiledInstance(g, tg).sweep_supported("scalar") is False
    sched = Scheduler(tg, policy=_pol(), backend="pallas")
    with pytest.warns(RuntimeWarning, match="injected backend build"):
        plan = sched.submit(g)
    assert plan.fallback is not None and plan.fallback[0][0] == "pallas"
    _assert_same_decisions(plan, _scalar_reference(tg, g))
