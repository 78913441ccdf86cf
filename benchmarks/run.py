"""Benchmark orchestrator — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick versions
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale sizes
    PYTHONPATH=src python -m benchmarks.run --only table5
"""
from __future__ import annotations

import argparse
import time
import traceback

SUITES = {
    "table3": ("bench_intrinsic", "Table 3: intrinsic efficiency"),
    "table4": ("bench_scalability", "Table 4/Fig 9: scalability"),
    "table5": ("bench_ml_utility", "Table 5: downstream ML utility"),
    "fig5": ("bench_variance", "Fig 5/6 + App E: variance-aware filtering"),
    "fig7": ("bench_estimators", "Fig 7: estimator stability/oversampling"),
    "fig10": ("bench_fidelity", "Fig 10: approximation fidelity"),
    "kernels": ("bench_kernels", "Pallas kernels vs oracles"),
    "engine": ("bench_engine", "Engine throughput (events/s, BENCH_engine.json)"),
    "serving": ("bench_serving",
                "Serving tier: open-loop tail latency vs offered load"),
    "roofline": ("bench_roofline", "Roofline terms from dry-run artifacts"),
}

QUICK_KW = {
    "table3": dict(n_events=8_000),
    "table4": dict(n_events=6_000),
    "table5": dict(regimes=("fraud", "ibm"), n_seeds=2, n_events=40_000,
               anomaly_boost=10.0),
    "fig10": dict(n_events=20_000, lambdas_pm=(0.002, 0.02, 0.2)),
    "fig5": dict(alphas=(0.0, 1.0, 3.0)),
    "engine": dict(n_events=16_384),
    "serving": dict(n_events=6_000),
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated suite names")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    names = list(SUITES) if not args.only else args.only.split(",")
    failures = []
    for name in names:
        mod_name, desc = SUITES[name]
        print(f"\n=== {name}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            kw = {} if args.full else QUICK_KW.get(name, {})
            mod.run(**kw)
            print(f"=== {name} done in {time.time() - t0:.1f}s ===",
                  flush=True)
        except Exception:
            failures.append(name)
            print(f"=== {name} FAILED ===")
            traceback.print_exc()
    print(f"\nbenchmarks complete; failures: {failures or 'none'}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
