"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--json]

Default output is ``name,us_per_call,derived`` CSV (one row per
measurement); ``--json`` emits the same rows as NDJSON — one JSON object
per line — for machine consumption (BENCH_*.json trajectory tracking).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

MODULES = [
    "benchmarks.fig2_dgemm_model",      # Fig 2: DGEMM model fit, R^2
    "benchmarks.fig56_hpl_accuracy",    # Fig 5/6: measured vs simulated
    "benchmarks.fig7_scalability",      # Fig 7: sim cost vs rank count
    "benchmarks.table2_top500",         # Table II: Frontera / PupMaya
    "benchmarks.sec5_whatif",           # §V: what-if analyses
    "benchmarks.sweep_bench",           # batched sweep engine vs loop
    "benchmarks.tpu_predict",           # TPU adaptation table
    "benchmarks.train_step",            # transformer workload sweep
    "benchmarks.top500_fleet",          # TOP500 list fleet prediction
    "benchmarks.trace_breakdown",       # trace-derived comm/compute split
    "benchmarks.kernels_bench",         # Pallas kernels
    "benchmarks.faults_bench",          # degraded fleet + hardened serve
    "benchmarks.engine_bench",          # DES hot loop vs frozen legacy
    "benchmarks.serve_bench",           # serving throughput + latency
    "benchmarks.campaign_bench",        # campaign matrix + edition study
]

# --smoke: the fast subset CI runs on every push so benchmark entry
# points can't silently rot (fig56/fig7 drive multi-minute DES runs and
# stay out; they are exercised by --full trajectory runs).
SMOKE_MODULES = [
    "benchmarks.fig2_dgemm_model",
    "benchmarks.table2_top500",
    "benchmarks.sec5_whatif",
    "benchmarks.sweep_bench",
    "benchmarks.tpu_predict",
    "benchmarks.train_step",
    "benchmarks.top500_fleet",
    "benchmarks.trace_breakdown",
    "benchmarks.faults_bench",
    "benchmarks.engine_bench",
    "benchmarks.serve_bench",
    "benchmarks.campaign_bench",
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full-size benchmark configs (slow)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module suffixes")
    ap.add_argument("--json", action="store_true",
                    help="emit NDJSON rows instead of CSV")
    ap.add_argument("--smoke", action="store_true",
                    help="fast CI subset (quick configs, no DES-heavy "
                         "modules)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernels of kernels_bench in "
                         "interpret mode (off the chip only)")
    args = ap.parse_args()

    if args.smoke and args.full:
        ap.error("--smoke and --full are mutually exclusive")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    modules = SMOKE_MODULES if args.smoke else MODULES
    if not args.json:
        print("name,us_per_call,derived")
    failed = 0
    for mod_name in modules:
        if args.only and not any(mod_name.endswith(o)
                                 for o in args.only.split(",")):
            continue
        try:
            mod = __import__(mod_name, fromlist=["run"])
            kw = ({"interpret": True} if args.interpret
                  and mod_name == "benchmarks.kernels_bench" else {})
            rows = mod.run(quick=not args.full, **kw)
            for r in rows:
                if args.json:
                    print(json.dumps(r), flush=True)
                else:
                    print(f"{r['name']},{r['us_per_call']:.2f},"
                          f"\"{r['derived']}\"", flush=True)
        except Exception as exc:
            failed += 1
            if args.json:
                print(json.dumps({"name": mod_name, "us_per_call": None,
                                  "derived": "ERROR",
                                  "error": f"{type(exc).__name__}: {exc}"}),
                      flush=True)
            else:
                print(f"{mod_name},NaN,\"ERROR\"", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
