"""The in-process recorder (``repro.tracing``) and its call sites.

* **Off costs a global check** — while off, ``span`` hands back one
  shared null context and ``count`` returns at once: no profiler
  annotation is made and no clock is read.
* **Totals** — nested spans give each its own ``self_s`` (duration less
  its children), counters sum, ``reset``/``snapshot`` start and read a
  fresh window.
* **Request ids** — the root span of a thread allocates ``rid`` and the
  spans opened inside it carry the same one; two threads keep separate
  stacks.
* **Call sites** — a fresh HVLB_CC(B) ``submit`` on the pallas backend
  records each span of the plan path once, but ``repro.backend.decode``
  twice (the winners' rows of the one dispatch, then the decode of the
  one alpha read, alpha*) and ``repro.api.prepare`` once at each of its
  three sites, and plans exactly as it does with the recorder off; the
  scan's jitted functions name its XLA module.
"""
import glob
from collections import Counter
import os
import re
import sys
import threading
import types
import warnings

import numpy as np
import pytest

from repro import tracing
from repro.core import HVLB_CC_B, Scheduler, paper_topology, random_spg

PLAN_SPANS = ("repro.api.submit", "repro.api.prepare", "repro.backend.build",
              "repro.backend.tables", "repro.backend.stage",
              "repro.backend.launch", "repro.backend.fetch",
              "repro.backend.decode", "repro.engine.assemble")


@pytest.fixture(autouse=True)
def clean_recorder():
    tracing.disable()
    tracing.reset()
    yield
    tracing.disable()
    tracing.reset()


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each
    span's name and stats in the order the spans open."""

    def __init__(self):
        self.made = []
        self._lock = threading.Lock()

    def __call__(self, name, **stats):
        with self._lock:
            self.made.append((name, stats))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


@pytest.fixture
def annotations(monkeypatch):
    jax = pytest.importorskip("jax")
    fake = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", fake)
    return fake


def _clock(monkeypatch, *ticks_ns):
    it = iter(ticks_ns)
    monkeypatch.setattr(tracing, "time",
                        types.SimpleNamespace(perf_counter_ns=it.__next__))


# ------------------------------------------------------------------ off
def test_off_records_nothing_and_touches_neither_profiler_nor_clock(
        monkeypatch):
    jax = pytest.importorskip("jax")

    def boom(*a, **k):
        raise AssertionError("touched while the recorder is off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    monkeypatch.setattr(tracing, "time",
                        types.SimpleNamespace(perf_counter_ns=boom))
    assert not tracing.enabled()
    assert tracing.span("a") is tracing.span("b", rid=3)
    with tracing.span("repro.api.submit"):
        with tracing.span("repro.backend.fetch"):
            tracing.count("backend.d2h_bytes", 10)
    assert tracing.snapshot() == {"spans": {}, "counters": {}}


# ------------------------------------------------------------- totals
def test_nested_spans_give_self_time(monkeypatch, annotations):
    # outer opens at 0, inner 10-40, a second inner 50-55, outer closes
    # at 100
    _clock(monkeypatch, 0, 10, 40, 50, 55, 100)
    tracing.enable()
    with tracing.span("outer"):
        with tracing.span("inner"):
            pass
        with tracing.span("inner"):
            pass
    spans = tracing.snapshot()["spans"]
    assert spans["outer"] == {"count": 1, "total_s": 100e-9,
                              "self_s": pytest.approx(65e-9)}
    assert spans["inner"] == {"count": 2, "total_s": pytest.approx(35e-9),
                              "self_s": pytest.approx(35e-9)}


def test_counters_sum_and_reset_starts_a_fresh_window(annotations):
    tracing.enable()
    tracing.count("backend.launches")
    tracing.count("backend.launches")
    tracing.count("backend.d2h_bytes", 4096)
    with tracing.span("a"):
        pass
    snap = tracing.snapshot()
    assert snap["counters"] == {"backend.launches": 2,
                                "backend.d2h_bytes": 4096}
    assert snap["spans"]["a"]["count"] == 1
    snap["counters"]["backend.launches"] = 99       # a copy
    assert tracing.snapshot()["counters"]["backend.launches"] == 2
    tracing.reset()
    assert tracing.snapshot() == {"spans": {}, "counters": {}}
    tracing.disable()
    tracing.count("backend.launches")
    with tracing.span("a"):
        pass
    assert tracing.snapshot() == {"spans": {}, "counters": {}}


def test_exception_closes_the_span(annotations):
    tracing.enable()
    with pytest.raises(ValueError):
        with tracing.span("outer"):
            with tracing.span("inner"):
                raise ValueError("boom")
    with tracing.span("next"):
        pass
    spans = tracing.snapshot()["spans"]
    assert {k: v["count"] for k, v in spans.items()} == {
        "outer": 1, "inner": 1, "next": 1}
    # the stack unwound: the next span is a root with a fresh id
    rids = [stats["rid"] for _, stats in annotations.made]
    assert rids[0] == rids[1] != rids[2]


# ---------------------------------------------------------- request ids
def test_children_share_the_roots_rid(annotations):
    tracing.enable()
    with tracing.span("repro.api.update", tenant="t1"):
        with tracing.span("repro.api.submit"):
            with tracing.span("repro.backend.fetch"):
                pass
    with tracing.span("repro.api.submit"):
        pass
    (n0, s0), (n1, s1), (n2, s2), (n3, s3) = annotations.made
    assert (n0, n1, n2, n3) == ("repro.api.update", "repro.api.submit",
                                "repro.backend.fetch", "repro.api.submit")
    assert s0["tenant"] == "t1"
    assert s0["rid"] == s1["rid"] == s2["rid"] != s3["rid"]


def test_threads_keep_separate_stacks(annotations):
    tracing.enable()
    both_open = threading.Barrier(2)

    def request(name):
        with tracing.span(name):
            both_open.wait(timeout=10)    # the other thread's root is open
            with tracing.span(name + ".child"):
                both_open.wait(timeout=10)

    threads = [threading.Thread(target=request, args=(n,))
               for n in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    rid = {name: stats["rid"] for name, stats in annotations.made}
    assert rid["a"] == rid["a.child"] != rid["b"] == rid["b.child"]
    spans = tracing.snapshot()["spans"]
    for name in ("a", "b"):
        # each root's only child is its own thread's
        assert spans[name]["self_s"] == pytest.approx(
            spans[name]["total_s"] - spans[name + ".child"]["total_s"])


def test_totals_lose_no_update_under_thread_contention(annotations):
    workers = (os.cpu_count() or 4) + 2
    spans_each, counts_each = 50, 500
    tracing.enable()
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(spans_each):
                with tracing.span("repro.api.update"):
                    with tracing.span("repro.backend.fetch"):
                        pass
            for _ in range(counts_each):
                tracing.count("backend.d2h_bytes", 3)

        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    snap = tracing.snapshot()
    assert snap["counters"]["backend.d2h_bytes"] == \
        3 * counts_each * workers
    for name in ("repro.api.update", "repro.backend.fetch"):
        assert snap["spans"][name]["count"] == spans_each * workers
    # one id per root span, shared with its child alone
    rids = Counter(s["rid"] for _, s in annotations.made)
    assert len(rids) == spans_each * workers
    assert set(rids.values()) == {2}


def test_spans_land_on_the_profiler_trace(tmp_path):
    """A span is a real profiler annotation: its bare name and ``rid``
    are in the recorded ``.xplane.pb``."""
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    tracing.enable()
    jax.profiler.start_trace(str(tmp_path))
    with tracing.span("repro.api.submit"):
        with tracing.span("repro.backend.fetch"):
            pass
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1
    found = {}
    with warnings.catch_warnings():
        # the profiler's stats type warns as it is first built
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(files[0]).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro."):
                        found[ev.name] = dict(ev.stats)
    assert set(found) == {"repro.api.submit", "repro.backend.fetch"}
    assert found["repro.api.submit"]["rid"] == \
        found["repro.backend.fetch"]["rid"]


# ----------------------------------------------------------- call sites
def _case():
    tg = paper_topology()
    g = random_spg(20, np.random.default_rng(5), ccr=1.0, tg=tg,
                   outdeg_constraint=True)
    return tg, g, HVLB_CC_B(alpha_max=1.0, alpha_step=0.25)


def test_submit_records_each_span_of_the_plan_path(annotations):
    pytest.importorskip("jax")
    tg, g, pol = _case()
    off = Scheduler(tg, policy=pol, backend="pallas").submit(g)
    assert tracing.snapshot() == {"spans": {}, "counters": {}}
    tracing.enable()
    on = Scheduler(tg, policy=pol, backend="pallas").submit(g)
    snap = tracing.snapshot()
    tracing.disable()
    assert on.backend == "pallas" and on.fallback is None
    # once each; prepare at its three sites: the session, its queue
    # and its compiled instance; decode for the dispatch and for the one
    # alpha decoded
    c = snap["counters"]
    assert c["backend.alphas_decoded"] == 1
    assert {k: v["count"] for k, v in snap["spans"].items()} == \
        {name: {"repro.api.prepare": 3, "repro.backend.decode": 2}.get(
            name, 1) for name in PLAN_SPANS}
    # every span of the request carries the root's id
    assert [n for n, _ in annotations.made][0] == "repro.api.submit"
    assert len({s["rid"] for _, s in annotations.made}) == 1
    # the children lie inside the root
    sub = snap["spans"]["repro.api.submit"]
    inner = sum(snap["spans"][n]["total_s"] for n in PLAN_SPANS[1:])
    assert sub["self_s"] == pytest.approx(sub["total_s"] - inner)
    assert 0 <= sub["self_s"] <= sub["total_s"]
    assert c["backend.launches"] == 1
    assert c["backend.h2d_bytes"] > 0 and c["backend.d2h_bytes"] > 0
    # the same plan as with the recorder off
    assert on.best_alpha == off.best_alpha
    assert np.array_equal(on.sweep.makespans, off.sweep.makespans)
    for a, b in ((on.schedule, off.schedule),):
        assert np.array_equal(a.proc, b.proc)
        assert np.array_equal(a.start, b.start)
        assert np.array_equal(a.finish, b.finish)
        assert a.messages.keys() == b.messages.keys()


def test_fetch_counter_is_the_fetched_arrays(monkeypatch):
    """``backend.d2h_bytes`` counts what the one fetch of a dispatch
    returned, ``backend.h2d_bytes`` the host arrays staged for it plus
    the uploads of the evaluator's set-up and constant tables, the edge
    CTML table as uploaded: factored, and expanded on the device."""
    pytest.importorskip("jax")
    import repro.core.backends.pallas as pb
    from repro.core.backends.layout import factored_edge_ct

    fetched, staged, ct = [], [], []
    dispatch = pb.PallasBackend._scan_dispatch

    def seen(self, waves, alphas):
        out = dispatch(self, waves, alphas)
        fetched.append(sum(a.nbytes for a in out))
        q, idx = factored_edge_ct(self.inst, self._R, self._H, self._Pp,
                                  self._Ep, self._np_dtype)
        ct.append(q.nbytes + idx.nbytes)
        tables = [a for k, a in enumerate(self._scan_dev) if k != 3]
        staged.append(sum(a.nbytes for a in self._pad) +
                      sum(a.nbytes for a in tables) + ct[0])
        return out

    monkeypatch.setattr(pb.PallasBackend, "_scan_dispatch", seen)
    tg, g, pol = _case()
    tracing.enable()
    Scheduler(tg, policy=pol, backend="pallas").submit(g)
    c = tracing.snapshot()["counters"]
    assert c["backend.d2h_bytes"] == fetched[0]
    assert c["backend.ct_bytes"] == ct[0]
    assert c["backend.h2d_bytes"] > staged[0]        # + the dispatch's


def test_update_and_faults_carry_their_own_root(annotations):
    pytest.importorskip("jax")
    tg, g, pol = _case()
    s = Scheduler(tg, policy=pol, backend="pallas")
    s.submit(g)
    tracing.enable()
    s.probe_update(task_rates={3: 1.5})
    s.update(task_rates={3: 1.5})
    s.mark_failed(proc=1)
    s.restore(proc=1)
    s.degrade(task=2, factor=2.0)
    spans = tracing.snapshot()["spans"]
    for name in ("probe_update", "update", "mark_failed", "restore",
                 "degrade"):
        assert spans[f"repro.api.{name}"]["count"] == (
            2 if name == "update" else 1)        # degrade(task=) updates
    assert spans["repro.api.prepare"]["count"] >= 4
    roots = [n for n, _ in annotations.made
             if n in ("repro.api.probe_update", "repro.api.update",
                      "repro.api.mark_failed", "repro.api.restore",
                      "repro.api.degrade")]
    assert len(roots) == 6
    by_rid = {}
    for name, stats in annotations.made:
        by_rid.setdefault(stats["rid"], []).append(name)
    # one id per public call: degrade's nested update shares its id
    assert len(by_rid) == 5


def test_scan_names_its_xla_module(monkeypatch):
    """The whole-schedule scan lowers to a module named after its
    jitted function, with the wave step under its named scope."""
    pytest.importorskip("jax")
    import repro.core.backends.pallas as pb

    runs = []
    scan_run = pb._scan_run

    def keep(*key):
        run = scan_run(*key)

        def called(*args):
            runs.append((run, args, key[-1]))
            return run(*args)
        return called

    monkeypatch.setattr(pb, "_scan_run", keep)
    tg, g, pol = _case()
    Scheduler(tg, policy=pol, backend="pallas").submit(g)
    Scheduler(tg, policy=HVLB_CC_B(alpha_max=0.0, alpha_step=0.25),
              backend="pallas").submit(g)
    names = set()
    for run, args, f32 in runs:
        with pb._x64(f32):
            text = run.lower(*args).as_text(debug_info=True)
        names.add(text.split("module @", 1)[1].split()[0])
        # the scope names the operations of the scan's body
        assert re.search(r"repro\.scan\.wave\)?/while/body/", text)
    assert names == {"jit_repro_scan_sweep", "jit_repro_scan"}
