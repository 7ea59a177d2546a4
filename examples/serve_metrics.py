"""Observe a serving run: scrape-ready metrics from one mixed wave.

    PYTHONPATH=src python examples/serve_metrics.py [--manifest runs.ndjson]

Pushes one mixed wave — healthy HPL, a faulted (straggler) scenario, a
transformer step, and a breakdown-DES request — through
``PredictionService``, then prints what an operator would see:

  * the Prometheus text exposition (``svc.prometheus()``) — request
    counters, queue-depth peak, wave sizes, per-request latency
    histogram, engine events/s from the breakdown DES;
  * the per-request latency quantiles straight off the registry;
  * one NDJSON run-manifest line (``svc.manifest()``) — the per-run
    artifact the campaign layer aggregates, optionally appended to an
    NDJSON journal with ``--manifest``.

Everything here is the service's own always-on registry: no flags were
passed, and the simulated numbers are bit-identical to a metrics-off
run (pass ``metrics=NULL_METRICS`` to opt out).
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.faults import FaultSpec
from repro.serve import PredictionService, WorkloadRequest


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--manifest", default=None, metavar="PATH",
                    help="append the run-manifest line to this NDJSON "
                         "journal")
    args = ap.parse_args(argv)

    svc = PredictionService()
    hpl = dict(N=1536, nb=128, P=2, Q=2, lookahead=0)
    out = svc.predict_batch([
        WorkloadRequest(rid=0, workload="hpl", platform="bdw-local",
                        params=dict(hpl)),
        WorkloadRequest(rid=1, workload="hpl", platform="bdw-local",
                        params=dict(hpl),
                        faults=FaultSpec.straggler(rank=1, slowdown=2.0)),
        WorkloadRequest(rid=2, workload="transformer",
                        platform="tpu-v5e-pod",
                        params={"mesh": (2, 4), "num_layers": 2}),
        WorkloadRequest(rid=3, workload="hpl", platform="bdw-local",
                        params=dict(hpl), breakdown=True),
    ])
    print(f"served {len(out)} predictions "
          f"(healthy {out[0]['time_s']:.3f}s, "
          f"straggler {out[1]['time_s']:.3f}s, "
          f"step {out[2]['step_s'] * 1e3:.2f}ms, "
          f"breakdown phases: "
          f"{sorted(out[3]['breakdown']['phases'])})")

    print("\n--- Prometheus scrape (svc.prometheus()) " + "-" * 24)
    print(svc.prometheus(), end="")

    lat = svc.metrics.histogram("serve.request_latency_s")
    print("--- request latency " + "-" * 45)
    for q in (0.50, 0.95, 0.99):
        print(f"  p{int(q * 100):<3} {lat.quantile(q) * 1e3:8.2f} ms")

    line = (svc.manifest() if args.manifest is None else None)
    if args.manifest:
        from repro.obs import append_manifest
        line = append_manifest(args.manifest, "serve_run",
                               meta={"example": "serve_metrics",
                                     "stats": dict(svc.stats)},
                               metrics=svc.metrics)
        print(f"\n--- manifest line appended to {args.manifest} " + "-" * 12)
    else:
        print("\n--- NDJSON run manifest (svc.manifest()) " + "-" * 24)
    print(line)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
