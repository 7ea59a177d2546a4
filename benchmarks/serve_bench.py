"""Serving-throughput benchmark: mixed waves through PredictionService.

Drives mixed (workload, platform, faults) request waves through the
micro-batching front end and reports the serving numbers that matter
for the paper's simulation-as-a-service claim: predictions/s and the
per-request latency distribution (p50/p95/p99 from the service's own
``serve.request_latency_s`` histogram — the metrics subsystem measuring
the service that carries it).  A sequential reference (same requests,
one ``predict()`` call each) runs in the same process, so the
batched/sequential throughput ratio is a machine-speed-normalized
number CI can gate on.

Also measured every run:

  * warm pool — cold first wave (every compile) vs a warmed service's
    first wave, which must pay ZERO sweep compiles (gated via the §18
    trace counters after force-cooling the compile caches);
  * result cache — the same repeated-cell mixed wave with the
    content-addressed cache on vs off: predictions/s both ways,
    hit-rate, and the cached/uncached speedup (gated: >=10x absolute
    and within tolerance of the committed baseline);
  * the cost of the metrics subsystem itself — the same wave with
    ``metrics=NULL_METRICS`` vs an enabled registry (acceptance:
    metrics-on overhead stays within noise, target <=2%).

Standalone use writes the NDJSON trajectory file CI gates on::

    PYTHONPATH=src python benchmarks/serve_bench.py --json \
        --out BENCH_serve.json

    # CI regression gate: fail if the machine-normalized throughput
    # (batched/sequential ratio) drops >20% vs the committed baseline
    PYTHONPATH=src python benchmarks/serve_bench.py --check BENCH_serve.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time

# normalized-throughput regression tolerance for --check (CI smoke gate)
CHECK_TOLERANCE = 0.20
# the acceptance floor for the cached/uncached throughput ratio on a
# repeated-cell mixed wave (ISSUE 10: >= 10x with the cache on)
MIN_CACHE_SPEEDUP = 10.0


def _requests(n_hpl, n_tf, n_faulted, n_breakdown):
    """The mixed scenario list: HPL + transformer + faulted HPL +
    breakdown-DES HPL.  Sweep shapes stay inside one compile bucket per
    family; the breakdown requests add real DES wall so waves carry a
    production-shaped mix of sub-ms sweeps and multi-ms simulations."""
    from repro.faults import FaultSpec
    from repro.serve import WorkloadRequest

    reqs = []
    rid = 0
    for i in range(n_hpl):
        reqs.append(WorkloadRequest(
            rid=rid, workload="hpl", platform="frontera",
            params=dict(N=1536 + 128 * (i % 4), nb=128, P=2, Q=4,
                        lookahead=0)))
        rid += 1
    for i in range(n_tf):
        reqs.append(WorkloadRequest(
            rid=rid, workload="transformer", platform="tpu-v5e-pod",
            params={"mesh": (2, 4), "num_layers": 2 + (i % 3)}))
        rid += 1
    spec = FaultSpec.straggler(rank=1, slowdown=2.0, seed=7)
    for i in range(n_faulted):
        reqs.append(WorkloadRequest(
            rid=rid, workload="hpl", platform="frontera",
            params=dict(N=1536, nb=128, P=2, Q=4, lookahead=0),
            faults=spec))
        rid += 1
    for i in range(n_breakdown):
        reqs.append(WorkloadRequest(
            rid=rid, workload="hpl", platform="bdw-local",
            params=dict(N=1536, nb=128, P=2, Q=2, lookahead=0),
            breakdown=True))
        rid += 1
    return reqs


def _wave_once(metrics=None):
    """One batched wave through a fresh service; returns (wall, svc)."""
    from repro.serve import PredictionService

    reqs = _requests(*_MIX)
    svc = (PredictionService() if metrics is None
           else PredictionService(metrics=metrics))
    t0 = time.perf_counter()
    svc.predict_batch(reqs)
    return time.perf_counter() - t0, svc


_MIX = (16, 16, 8, 4)       # hpl, transformer, faulted, breakdown / wave


def run(quick: bool = True):
    from repro.core import fastsim
    from repro.obs import NULL_METRICS
    from repro.serve import PredictionService
    from repro.workloads import stepsim

    global _MIX
    _MIX = (16, 16, 8, 4) if quick else (64, 64, 32, 8)
    n_req = sum(_MIX)
    rows = []

    # ------------------------------- warm pool: cold vs warm first wave
    # This section MUST run first: it measures the compile bill of a
    # pristine process.  Cold = first wave eats every sweep compile.
    # Then the compile caches are force-cooled (cache_clear) and a
    # fresh service warms from a representative traffic sample — its
    # first real wave must pay ZERO compiles (gated in --check via the
    # trace counters, the §18 ground truth).
    def _traces():
        return fastsim.trace_count() + stepsim.trace_count()

    pre = _traces()
    wall_cold, _ = _wave_once()
    cold_compiles = _traces() - pre

    fastsim._compiled.cache_clear()            # re-cool the process
    stepsim._compiled.cache_clear()
    svc_w = PredictionService()
    warm_report = svc_w.warm(requests=_requests(*_MIX))
    pre = _traces()
    t0 = time.perf_counter()
    svc_w.predict_batch(_requests(*_MIX))
    wall_warm = time.perf_counter() - t0
    first_wave_compiles = _traces() - pre
    rows.append({
        "name": "serve.warm_first_wave",
        "us_per_call": wall_warm / n_req * 1e6,
        "cold_first_wave_s": wall_cold,
        "warm_first_wave_s": wall_warm,
        "first_wave_compiles": first_wave_compiles,
        "warm_compiles": warm_report["compiles"],
        "derived": f"cold={wall_cold * 1e3:.0f}ms;"
                   f"warm={wall_warm * 1e3:.0f}ms;"
                   f"speedup={wall_cold / wall_warm:.1f}x;"
                   f"warm_compiles={warm_report['compiles']};"
                   f"first_wave_compiles={first_wave_compiles}"})

    # ------------------------------------------- batched mixed wave
    _wave_once()                               # warm the compile caches
    best_wall, best_svc = None, None
    for _ in range(5):
        wall, svc = _wave_once()
        if best_wall is None or wall < best_wall:
            best_wall, best_svc = wall, svc
    h = best_svc.metrics.histogram("serve.request_latency_s")
    p50, p95, p99 = (h.quantile(q) for q in (0.50, 0.95, 0.99))
    pps = n_req / best_wall

    # ------------------------------- sequential reference (same work)
    # a stratified every-4th subset (so it includes breakdown requests
    # in proportion), served one single-request wave at a time; its own
    # warm pass first — single-lane sweeps compile separately — then
    # best-of-3 timed passes (min, same estimator as the batched side,
    # so the gate ratio is min/min and stays stable under load noise)
    svc_seq = PredictionService()
    for r in _requests(*_MIX)[::4]:
        svc_seq.predict_batch([r])             # warm the 1-lane caches
    seq_wall, seq_n = None, len(_requests(*_MIX)[::4])
    for _ in range(3):
        seq_reqs = _requests(*_MIX)[::4]
        t0 = time.perf_counter()
        for r in seq_reqs:
            svc_seq.predict_batch([r])
        w = time.perf_counter() - t0
        seq_wall = w if seq_wall is None else min(seq_wall, w)
    seq_pps = seq_n / seq_wall

    rows.append({
        "name": "serve.mixed_wave",
        "us_per_call": best_wall / n_req * 1e6,
        "predictions_per_s": pps,
        "seq_predictions_per_s": seq_pps,
        "p50_s": p50, "p95_s": p95, "p99_s": p99,
        "derived": f"requests={n_req};predictions_per_s={pps:.0f};"
                   f"seq={seq_pps:.0f}/s;"
                   f"norm_ratio={pps / seq_pps:.2f}x;"
                   f"p50={p50 * 1e3:.2f}ms;p95={p95 * 1e3:.2f}ms;"
                   f"p99={p99 * 1e3:.2f}ms"})

    # ----------------------- result cache: repeated-cell wave, on vs off
    # Fleet traffic is mostly duplicate cells (the campaign layer asks
    # the same matrix across editions/users).  Serve the SAME mixed wave
    # repeatedly: cache-off recomputes every sweep + breakdown DES;
    # cache-on answers from content-addressed hits.  Both sides use the
    # best-of-5 min estimator on a service that has already seen the
    # traffic once (steady state), so the ratio is machine-normalized.
    svc_u = PredictionService()
    svc_u.predict_batch(_requests(*_MIX))      # steady-state entry
    wall_u = None
    for _ in range(5):
        reqs = _requests(*_MIX)
        t0 = time.perf_counter()
        svc_u.predict_batch(reqs)
        w = time.perf_counter() - t0
        wall_u = w if wall_u is None else min(wall_u, w)
    uncached_pps = n_req / wall_u

    svc_c = PredictionService(cache=True)
    svc_c.predict_batch(_requests(*_MIX))      # populate pass (misses)
    wall_c = None
    for _ in range(5):
        reqs = _requests(*_MIX)
        t0 = time.perf_counter()
        svc_c.predict_batch(reqs)
        w = time.perf_counter() - t0
        wall_c = w if wall_c is None else min(wall_c, w)
    cached_pps = n_req / wall_c
    hits = svc_c.stats["cache_hits"]
    misses = svc_c.stats["cache_misses"]
    hit_rate = hits / max(hits + misses, 1)
    ratio = cached_pps / uncached_pps
    rows.append({
        "name": "serve.cached_wave",
        "us_per_call": wall_c / n_req * 1e6,
        "predictions_per_s": cached_pps,
        "uncached_predictions_per_s": uncached_pps,
        "cache_speedup": ratio,
        "hit_rate": hit_rate,
        "derived": f"cached={cached_pps:.0f}/s;uncached={uncached_pps:.0f}/s;"
                   f"speedup={ratio:.1f}x;hit_rate={hit_rate:.2f};"
                   f"coalesced={svc_c.stats['coalesced']}"})

    # ------------------------------------- metrics-subsystem overhead
    # interleaved, order-alternating best-of-8 (noise on a ~30ms wave
    # swamps a one-shot comparison); min-vs-min isolates the
    # systematic cost from scheduler/GC jitter
    walls_off, walls_on = [], []
    for i in range(8):
        if i % 2 == 0:
            walls_off.append(_wave_once(metrics=NULL_METRICS)[0])
            walls_on.append(_wave_once()[0])
        else:
            walls_on.append(_wave_once()[0])
            walls_off.append(_wave_once(metrics=NULL_METRICS)[0])
    wall_off, wall_on = min(walls_off), min(walls_on)
    overhead = wall_on / wall_off - 1.0
    rows.append({
        "name": "serve.metrics_overhead",
        "us_per_call": (wall_on - wall_off) / n_req * 1e6,
        "overhead_frac": overhead,
        "derived": f"metrics_on={wall_on * 1e3:.1f}ms;"
                   f"metrics_off={wall_off * 1e3:.1f}ms;"
                   f"overhead={overhead * 100:+.1f}%"})

    # --------------------------- hardened wave: every counter nonzero
    # (retry + deadline fallback + isolated error in ONE wave; the
    # bench asserts the telemetry the acceptance scenario relies on)
    from repro.serve import WorkloadRequest
    from repro.workloads import HPLFastModel

    svc = PredictionService(backoff_s=0.001)
    orig = HPLFastModel.sweep_models.__func__
    state = {"n": 0}

    def flaky(cls, models, *, shard=False):
        state["n"] += 1
        if state["n"] == 1:
            raise RuntimeError("transient backend hiccup")
        return orig(cls, models, shard=shard)

    HPLFastModel.sweep_models = classmethod(flaky)
    try:
        t0 = time.perf_counter()
        out = svc.predict_batch(
            [WorkloadRequest(rid=0, workload="hpl", platform="frontera"),
             WorkloadRequest(rid=1, workload="transformer",
                             platform="tpu-v5e-pod",
                             params={"mesh": (2, 4), "num_layers": 2},
                             breakdown=True, timeout_s=1e-9),
             WorkloadRequest(rid=2, workload="hpl", platform="nope")],
            isolate_errors=True)
        wall = time.perf_counter() - t0
    finally:
        HPLFastModel.sweep_models = classmethod(orig)
    c = svc.metrics.snapshot()["counters"]
    assert out[2]["status"] == "error" and out[1]["degraded"]
    for key in ("serve.retries", "serve.deadline_fallbacks",
                "serve.errors_isolated"):
        assert c.get(key, 0) > 0, f"{key} stayed zero"
    rows.append({
        "name": "serve.hardened_wave",
        "us_per_call": wall / 3 * 1e6,
        "derived": f"retries={c['serve.retries']:.0f};"
                   f"deadline_fallbacks={c['serve.deadline_fallbacks']:.0f};"
                   f"errors_isolated={c['serve.errors_isolated']:.0f};"
                   f"wall={wall * 1e3:.1f}ms"})
    return rows


def check(rows, baseline_path: str) -> int:
    """CI gate: fail if (a) machine-normalized serving throughput
    (batched predictions/s over the in-process sequential reference)
    regressed >CHECK_TOLERANCE vs the committed baseline, (b) the
    cached/uncached throughput ratio on the repeated-cell wave dropped
    below MIN_CACHE_SPEEDUP or regressed >CHECK_TOLERANCE normalized
    vs baseline, or (c) the warm-pool first wave paid any sweep
    compiles.  Rows without a gate are informational."""
    base = {}
    with open(baseline_path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                r = json.loads(line)
                base[r["name"]] = r
    failures, gated = [], 0
    for r in rows:
        name = r["name"]
        b = base.get(name)
        if "first_wave_compiles" in r:
            gated += 1
            ok = r["first_wave_compiles"] == 0
            print(f"{name}: first wave after warm paid "
                  f"{r['first_wave_compiles']} compiles "
                  f"({'OK' if ok else 'REGRESSED'})")
            if not ok:
                failures.append(name)
            continue
        if "cache_speedup" in r:
            gated += 1
            ok = r["cache_speedup"] >= MIN_CACHE_SPEEDUP
            rel_txt = ""
            if b is not None and "cache_speedup" in b:
                rel = r["cache_speedup"] / b["cache_speedup"]
                ok = ok and rel >= 1.0 - CHECK_TOLERANCE
                rel_txt = f" vs baseline {b['cache_speedup']:.1f}x " \
                          f"({rel:.2f} relative)"
            print(f"{name}: cached/uncached {r['cache_speedup']:.1f}x"
                  f"{rel_txt} (floor {MIN_CACHE_SPEEDUP:.0f}x) "
                  f"{'OK' if ok else 'REGRESSED'}")
            if not ok:
                failures.append(name)
            continue
        if b is None:
            continue
        if "seq_predictions_per_s" in r and "seq_predictions_per_s" in b:
            now = r["predictions_per_s"] / r["seq_predictions_per_s"]
            ref = b["predictions_per_s"] / b["seq_predictions_per_s"]
            rel = now / ref
            gated += 1
            status = "OK" if rel >= 1.0 - CHECK_TOLERANCE else "REGRESSED"
            print(f"{name}: batched/sequential {now:.2f}x vs baseline "
                  f"{ref:.2f}x ({rel:.2f} relative) {status}")
            if status == "REGRESSED":
                failures.append(name)
        elif "overhead_frac" in r:
            print(f"{name}: metrics overhead "
                  f"{r['overhead_frac'] * 100:+.1f}% info-only")
    if failures:
        print(f"FAIL: normalized serving throughput regressed "
              f">{CHECK_TOLERANCE:.0%} vs {baseline_path} on: "
              f"{', '.join(failures)}")
        return 1
    print(f"serve bench within {CHECK_TOLERANCE:.0%} of baseline "
          f"({gated} gated scenarios)")
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--out", default=None,
                    help="also write NDJSON rows to this file")
    ap.add_argument("--check", default=None, metavar="BASELINE",
                    help="exit nonzero if normalized throughput regressed "
                         f">{CHECK_TOLERANCE:.0%} vs this NDJSON baseline")
    args = ap.parse_args()
    rows = run(quick=not args.full)
    lines = [json.dumps(r) for r in rows]
    if args.json:
        print("\n".join(lines))
    else:
        print("name,us_per_call,derived")
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.2f},\"{r['derived']}\"")
    if args.out:
        with open(args.out, "w") as fh:
            fh.write("\n".join(lines) + "\n")
    if args.check:
        sys.exit(check(rows, args.check))


if __name__ == "__main__":
    main()
