#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it runs on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the system under test in ``src/``.  The cell's configuration
(``bench/configs/<config>.json``) and traffic mix
(``bench/traffic/<traffic>.json``) are found by the names in
``BENCHMARK.json``; the traffic file names its load
(``bench/loads/<load>.py``), and each per-layer metric is read by
``bench/metrics/<metric>.py``.  So a cell, a configuration, a traffic
mix or a metric is added with new files only.

A run: set-up (``setup_s``: from process start through JAX's start, the
cell's data from ``--seed``, and the warm-up of every scan shape the
traffic uses, compilation included), then a window of ``--seconds``,
then the plain reference holds the plans served in the window to
account.  The last stderr lines give each number compared beside its
limit; the last stdout line is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (end-to-end with ``--trace 0``,
per-layer with ``--trace 1``), ``device``, with ``--trace 1`` the
``breakdown``, and last the ``checks``.

Exits nonzero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the system under test is missing.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()               # set-up is timed from here

import argparse                       # noqa: E402
import importlib                      # noqa: E402
import importlib.util                 # noqa: E402
import json                           # noqa: E402
import os                             # noqa: E402
import shutil                         # noqa: E402
import sys                            # noqa: E402
import tempfile                       # noqa: E402
from typing import Any, Dict, List, NoReturn, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# fixed, inside the checkout: the path is part of the cache's key
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# environment knobs that steer the program's device path off its default
STEERING_ENV = ("REPRO_PALLAS_INTERPRET", "REPRO_PALLAS_DTYPE",
                "REPRO_PALLAS_TILE", "REPRO_PALLAS_SCAN",
                "REPRO_SCHED_BACKEND", "REPRO_SCHED_WAVE_TIMEOUT")

if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from benchlib import trace as tracelib  # noqa: E402
from benchlib.cell import Context, Outcome  # noqa: E402
from benchlib.checks import Checker  # noqa: E402
from benchlib.probe import Probe  # noqa: E402


class BenchError(Exception):
    """The run cannot produce a result."""


def fail(msg: str) -> NoReturn:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Dict[str, Any]:
    spec = _load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    return {
        "spec": spec, "cell": cell,
        "config": _load_json(os.path.join(BENCH, "configs",
                                          cell["config"] + ".json")),
        "traffic": _load_json(os.path.join(BENCH, "traffic",
                                           cell["traffic"] + ".json")),
    }


def cell_metrics(spec: Dict[str, Any], cell: str, key: str) -> List[dict]:
    """The end-to-end or per-layer metrics a cell reports."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if key == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in names]


class Measurements:
    """What a per-layer metric reader may read."""

    def __init__(self, outcome: Outcome, probe: Probe,
                 summary: Optional[tracelib.Summary],
                 device_kind: str) -> None:
        self.outcome = outcome
        self.probe = probe
        self.summary = summary
        self.device_kind = device_kind

    @property
    def plans(self) -> int:
        return self.outcome.plans

    def span_s(self, name: str) -> Optional[float]:
        n, s = self.probe.total(name)
        return s if n else None


def read_metric(name: str, m: Measurements) -> Optional[dict]:
    path = os.path.join(BENCH, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    got = mod.read(m)
    if got is None:
        return None
    value, extra = got if isinstance(got, tuple) else (got, {})
    return {"value": float(value), **extra}


class Compiles:
    """JAX traces and compilations while the window is open, and the
    persistent cache's hits and misses in set-up (a second run of a cell
    in one checkout should miss nothing)."""

    def __init__(self) -> None:
        self.on = False
        self.traces = self.compiles = 0
        self.setup = {"cache_hits": 0, "cache_misses": 0}

    def install(self) -> None:
        import jax.monitoring as mon

        def seen(event: str, _secs: float, **_kw) -> None:
            if not self.on:
                return
            if event.endswith("/jaxpr_trace_duration"):
                self.traces += 1
            elif event.endswith("/backend_compile_duration"):
                self.compiles += 1

        def cached(event: str, **_kw) -> None:
            name = event.rsplit("/", 1)[-1]
            if not self.on and name in self.setup:
                self.setup[name] += 1

        mon.register_event_duration_secs_listener(seen)
        mon.register_event_listener(cached)


def run(argv: Optional[List[str]] = None, require_tpu: bool = True,
        found: Optional[Dict[str, Any]] = None) -> dict:
    """One run.  Tests pass ``require_tpu=False`` and their own small
    cell as ``found`` (``spec``, ``cell``, ``config``, ``traffic``)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=int, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise BenchError(f"the system under test is not in this checkout "
                         f"({src}/repro)")
    found = found or load_cell(args.workload)
    spec, cell = found["spec"], found["cell"]
    steering = [v for v in STEERING_ENV if v in os.environ]
    if steering and require_tpu:
        raise BenchError(f"unset {', '.join(steering)}: the benchmark runs "
                         f"the program's default device path")

    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    if src not in sys.path:
        sys.path.insert(0, src)
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX found no device: {e}")
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise BenchError(f"no TPU: JAX runs on {dev.platform}; the "
                         f"benchmark never falls back to the CPU")
    if len(devices) < cell["chips"]:
        raise BenchError(f"the cell asks for {cell['chips']} chips, JAX "
                         f"found {len(devices)}")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"compile_cache={os.environ['JAX_COMPILATION_CACHE_DIR']} "
          f"ready_at_s={time.perf_counter() - T0:.3f}",
          file=sys.stderr, flush=True)

    trace_on = bool(args.trace)
    probe = Probe(trace_on)
    checker = Checker(found["config"]["limits"])
    compiles = Compiles()
    compiles.install()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace_on \
        else None
    window: Dict[str, Any] = {}

    def open_window() -> float:
        """Start the window: spans and counters reset, the profiler
        started (``--trace 1``)."""
        if trace_on:
            # device operations and the benchmark's own annotations; no
            # Python function tracer, no runtime host events (they cost
            # more than the work they would time)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window["mark"] = jax.profiler.TraceAnnotation(tracelib.WINDOW_SPAN)
        window["mark"].__enter__()
        probe.open_window()
        compiles.on = True
        return time.perf_counter()

    def close_window() -> None:
        """End the window: spans and counters stop counting here."""
        compiles.on = False
        window.pop("mark").__exit__(None, None, None)
        probe.freeze()
        if trace_on:
            jax.profiler.stop_trace()

    ctx = Context(workload=args.workload, config=found["config"],
                  traffic=found["traffic"], seed=args.seed,
                  seconds=args.seconds, probe=probe, checker=checker,
                  open_window=open_window, close_window=close_window)
    load = importlib.import_module(
        "loads." + found["traffic"]["load"])
    try:
        outcome: Outcome = load.run(ctx)
        setup_s = outcome.window_start - T0
        probe.remove()
        print(f"window: seconds={outcome.window_s!r} "
              f"attempted={outcome.attempted} failed={outcome.failed} "
              f"jax_traces={compiles.traces} "
              f"compiles={compiles.compiles} (both should be 0); set-up "
              f"cache hits={compiles.setup['cache_hits']} "
              f"misses={compiles.setup['cache_misses']}",
              file=sys.stderr, flush=True)
        memory_peak = max(int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)) for d in devices[:cell["chips"]])
        t_ref = time.perf_counter()
        try:
            outcome.check()
        except Exception as e:          # a crash of the check is a fault
            checker.fault(f"reference check: {type(e).__name__}: {e}")
        print(f"reference: seconds={time.perf_counter() - t_ref:.3f}",
              file=sys.stderr, flush=True)
        summary = None
        if trace_on:
            tr = tracelib.load(trace_dir)
            summary = tracelib.summarize(tr)
            # fewer programs than launches: the profiler dropped events
            print(f"trace: window_s={summary.window_s!r} device programs "
                  f"{tr.programs}, launches {probe.counter('launches')}",
                  file=sys.stderr, flush=True)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    metrics: Dict[str, dict] = {}
    result: Dict[str, Any] = {}
    if trace_on:
        m = Measurements(outcome, probe, summary, dev.device_kind)
        for spec_m in cell_metrics(spec, cell["name"], "per_layer"):
            got = read_metric(spec_m["name"], m)
            if got is not None:
                metrics[spec_m["name"]] = {**got, "unit": spec_m["unit"]}
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    else:
        values = dict(outcome.end_to_end, setup_s=setup_s)
        for spec_m in cell_metrics(spec, cell["name"], "end_to_end"):
            metrics[spec_m["name"]] = {"value": values[spec_m["name"]],
                                       "unit": spec_m["unit"]}
    checks = checker.report()
    return {"correct": checker.correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": device,
            **result, "checks": checks}


def main() -> None:
    try:
        result = run()
    except BenchError as e:
        fail(str(e))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
