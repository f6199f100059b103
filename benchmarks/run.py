"""Benchmark driver — one module per paper table/figure.

Usage:  PYTHONPATH=src python -m benchmarks.run [--full] [--only exp1,exp3]
                                 [--engine compiled|reference]
                                 [--backend auto|scalar|vector]
                                 [--json [PATH]]

Emits ``name,us_per_call,derived`` CSV on stdout.  ``--full`` uses the
paper's sample sizes (100 graphs/point, 1000 DAGs for SFR, alpha to 20).
``--backend`` selects the compiled engine's candidate-evaluation backend
for experiments that accept it (exp7 additionally times the scalar and
vector backends against each other regardless).  ``--json`` additionally
writes a machine-readable snapshot (default ``BENCH_sched.json``) with
every row plus an engine-vs-reference speedup probe on the exp1
alpha-sweep workload (n=50, alpha_max=5, step=0.05) so the perf
trajectory is tracked across PRs.
"""
from __future__ import annotations

import argparse
import importlib
import inspect
import json
import sys
import time

from repro.compile_cache import use_compile_cache

MODULES = [
    "exp0_paper_example",
    "exp1_slr_speedup",
    "exp2_load_balance",
    "exp3_ccr",
    "exp4_sfr",
    "exp5_imprecise",
    "exp6_tpu_placement",
    "exp7_engine_scaling",    # compiled-engine throughput scaling
    "exp8_session_api",       # incremental update + fleet submit_many
    "exp9_faults",            # fault-recovery latency + prefix survival
    "exp10_service",          # serving layer: coalescing + replan tail
    "roofline",               # §Roofline summary rows from the dry-run
]


def engine_speedup_probe(n_graphs: int = 3, backend=None) -> dict:
    """Time the exp1 alpha-sweep workload (n=50, alpha_max=5, step=0.05)
    on the reference and compiled paths and assert identical results."""
    import numpy as np

    from repro.core import HVLB_CC_A, Scheduler, paper_topology, random_spg

    tg = paper_topology()
    policy = HVLB_CC_A(alpha_max=5.0, alpha_step=0.05)
    ref_us = eng_us = 0.0
    for k in range(n_graphs):
        rng = np.random.default_rng(1050 + k)
        g = random_spg(50, rng, ccr=1.0, tg=tg, outdeg_constraint=True)
        t0 = time.perf_counter()
        ref = Scheduler(tg, policy=policy, engine="reference").submit(g).sweep
        t1 = time.perf_counter()
        eng = Scheduler(tg, policy=policy, engine="compiled",
                        backend=backend).submit(g).sweep
        t2 = time.perf_counter()
        assert np.array_equal(ref.alphas, eng.alphas)
        assert np.array_equal(ref.makespans, eng.makespans)
        assert ref.best_alpha == eng.best_alpha
        assert np.array_equal(ref.best.finish, eng.best.finish)
        ref_us += (t1 - t0) * 1e6
        eng_us += (t2 - t1) * 1e6
    return {
        "workload": "exp1 n=50 alpha_max=5 step=0.05 (x%d graphs)" % n_graphs,
        "reference_us_per_call": ref_us / n_graphs,
        "engine_us_per_call": eng_us / n_graphs,
        "speedup": ref_us / eng_us,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sample sizes")
    ap.add_argument("--only", type=str, default="",
                    help="comma-separated exp prefixes to run")
    ap.add_argument("--engine", type=str, default="compiled",
                    choices=["compiled", "reference"],
                    help="scheduler implementation for the experiments")
    ap.add_argument("--backend", type=str, default=None,
                    choices=["auto", "scalar", "vector", "pallas"],
                    help="candidate-evaluation backend for the compiled "
                         "engine (default: auto / $REPRO_SCHED_BACKEND); "
                         "pallas requires jax and runs the device kernel "
                         "(interpret mode off-TPU)")
    ap.add_argument("--json", type=str, nargs="?", const="BENCH_sched.json",
                    default=None, metavar="PATH",
                    help="also write a JSON snapshot (incl. the "
                         "engine-vs-reference speedup probe)")
    args = ap.parse_args()
    only = [x.strip() for x in args.only.split(",") if x.strip()]
    use_compile_cache()

    all_rows = []
    print("name,us_per_call,derived")
    for mod_name in MODULES:
        if only and not any(mod_name.startswith(o) for o in only):
            continue
        try:
            mod = importlib.import_module(f"benchmarks.{mod_name}")
        except ModuleNotFoundError as e:
            print(f"# skipped {mod_name}: {e}", file=sys.stderr)
            continue
        kwargs = {"full": args.full}
        params = inspect.signature(mod.run).parameters
        if "engine" in params:
            kwargs["engine"] = args.engine
        if "backend" in params:
            kwargs["backend"] = args.backend
        for r in mod.run(**kwargs):
            all_rows.append(r)
            print(r)

    if args.json is not None:
        rows = []
        for r in all_rows:
            name, us, derived = r.split(",", 2)
            try:
                derived = float(derived)
            except ValueError:
                pass
            rows.append({"name": name, "us_per_call": float(us),
                         "derived": derived})
        snapshot = {
            "engine": args.engine,
            "backend": args.backend,
            "full": args.full,
            "engine_vs_reference": engine_speedup_probe(
                backend=args.backend),
            "rows": rows,
        }
        with open(args.json, "w") as f:
            json.dump(snapshot, f, indent=2)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
