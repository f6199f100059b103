"""Trace reduction: busy union, idle share and gap labels, on synthetic
intervals and on a small trace recorded on the CPU."""
import time

import pytest

from benchlib import trace as T


def test_union_gaps_and_attribution():
    busy = T.union([(0, 10), (5, 20), (30, 40), (40, 45)])
    assert busy == [(0, 20), (30, 45)]
    assert T.gaps(busy, 0, 50) == [(20, 30), (45, 50)]
    assert T.clip([(0, 10), (15, 25)], 5, 20) == [(5, 10), (15, 20)]
    spans = [(0, 100, "bench.window"), (10, 60, "bench.api.submit"),
             (20, 40, "bench.engine.decode")]
    assert T.segments(spans) == [(10, 20, "bench.api.submit"),
                                 (20, 40, "bench.engine.decode"),
                                 (40, 60, "bench.api.submit")]
    # an idle stretch split by what the host was doing in each part
    assert T.attribute(spans, [(15, 45), (70, 80)]) == {
        "bench.api.submit": 10, "bench.engine.decode": 20,
        "host.other": 10}


def test_summary_on_synthetic_trace():
    tr = T.Trace(device_ops=[[(10, 30, "fusion.1"), (20, 40, "fusion.2"),
                              (70, 80, "fusion.1")]],
                 host_spans=[(0, 100, "bench.window"),
                             (40, 70, "bench.engine.decode")])
    s = T.summarize(tr)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(40e-9)        # 10-40 and 70-80
    assert s.idle_pct == pytest.approx(60.0)
    assert s.device_ops[0] == ["fusion.1", pytest.approx(30e-9)]
    assert dict((k, v) for k, v in s.idle_gaps) == {
        "bench.engine.decode": pytest.approx(30e-9),
        "host.other": pytest.approx(30e-9)}


def _cpu_ops(plane: str, line: str) -> bool:
    # on the CPU, XLA runs its operations on these host threads
    return plane == "/host:CPU" and line.startswith("tf_XLA")


def test_recorded_cpu_trace(tmp_path):
    jax = pytest.importorskip("jax")
    if jax.default_backend() != "cpu":
        pytest.skip("records a CPU trace")
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x.T) @ x)
    x = jnp.ones((512, 512), jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(T.WINDOW_SPAN):
        f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("bench.test.sleep"):
            time.sleep(0.05)
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    s = T.summarize(T.load(str(tmp_path), _cpu_ops))
    assert 0.05 <= s.window_s < 5.0
    assert 0.0 < s.busy_s < s.window_s
    assert s.idle_pct == pytest.approx(100 * (1 - s.busy_s / s.window_s))
    assert s.device_ops and s.device_ops[0][1] > 0
    gaps = dict((k, v) for k, v in s.idle_gaps)
    assert gaps["bench.test.sleep"] >= 0.045
