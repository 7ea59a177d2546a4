"""Resilience what-ifs: predict degraded-platform performance before it
happens on the machine (DESIGN.md §16):

    PYTHONPATH=src python examples/whatif_faults.py

1. One declarative ``FaultSpec`` — a straggler chip at 0.5x plus a
   seeded 5% of links at half bandwidth — runs through BOTH backends:
   the event-level DES (with fault spans in the exportable Chrome
   trace) and the batched fastsim, which sweeps a whole degradation
   grid in one compiled program.
2. A fail-stop scenario runs on the DES (peers block, the run reports
   ``failed=True``) and feeds the elastic-restart planner: which
   data-parallel rows to evict and how to re-partition the batch.
3. The hardened PredictionService serves a budgeted breakdown request:
   blow the deadline and the response degrades to the fastsim answer,
   stamped with the reason, instead of timing out the wave.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.faults import FaultSpec
from repro.faults.fastsim import sweep_faults
from repro.ft import restart_plan_for_faults, simulate_fault_impact
from repro.platforms import get_platform
from repro.serve import PredictionService, WorkloadRequest
from repro.workloads import get_workload


def main():
    plat = get_platform("bdw-local")
    wl = get_workload("hpl", N=1536, nb=128, P=2, Q=4, lookahead=0)
    scenario = (FaultSpec.straggler(rank=1, slowdown=2.0, seed=7)
                + FaultSpec.degraded_links(0.05, factor=0.5, seed=7))

    print("== one scenario, two backends (HPL on bdw-local) ==")
    healthy = wl.predict_des(plat)
    des = wl.predict_des(plat, faults=scenario)
    fast = wl.predict(plat, faults=scenario)
    print(f"  healthy DES : {healthy['time_s']:.3f}s")
    print(f"  faulted DES : {des['time_s']:.3f}s "
          f"({des['time_s'] / healthy['time_s']:.2f}x)")
    print(f"  faulted fast: {fast['time_s']:.3f}s "
          f"(closed form, {abs(fast['time_s'] - des['time_s']) / des['time_s'] * 100:.1f}% off the DES)")

    app = wl.des_app(plat, trace=True, faults=scenario)
    app.run()
    out = Path("whatif_faults_trace.json")
    app.engine.trace.to_chrome_json(str(out))
    print(f"  Chrome trace with fault spans -> {out} (ui.perfetto.dev)")

    print("== degradation grid, one compiled sweep ==")
    specs = [FaultSpec.straggler(rank=1, slowdown=s, seed=7)
             + FaultSpec.degraded_links(0.05, factor=f, seed=7)
             for s in (1.5, 2.0, 4.0) for f in (0.75, 0.5)]
    for spec, row in zip(specs, sweep_faults(wl, plat, specs)[1:]):
        s, f = spec.faults[0].factor, spec.faults[1].factor
        print(f"  straggler x{s:.1f}, links x{f:.2f}: "
              f"{row['slowdown_vs_healthy']:.2f}x slower")

    print("== fail-stop -> elastic restart plan (transformer) ==")
    tf = get_workload("transformer", mesh=(2, 4), num_layers=3)
    dead = FaultSpec.fail_stop(rank=5, at=1e-4)
    impact = simulate_fault_impact(tf, "tpu-v5e-pod", dead, des=True)
    print(f"  DES verdict: {impact['verdict']} "
          f"(failed={impact.get('failed', False)}, "
          f"{impact.get('n_finished')}/8 ranks finished)")
    plan = restart_plan_for_faults(dead, global_batch=64, resume_step=1200,
                                   old_mesh=(2, 4))
    print(f"  restart on {plan.new_mesh}: per-device batch "
          f"{plan.per_device_batch_new}; {plan.notes}")

    print("== hardened serving: deadline -> fastsim fallback ==")
    svc = PredictionService()
    res = svc.predict_batch([
        WorkloadRequest(rid=0, workload="transformer",
                        platform="tpu-v5e-pod",
                        params={"mesh": [2, 4], "num_layers": 2},
                        breakdown=True, timeout_s=60.0),
        WorkloadRequest(rid=1, workload="transformer",
                        platform="tpu-v5e-pod",
                        params={"mesh": [4, 8], "num_layers": 8},
                        breakdown=True, timeout_s=1e-6),
    ])
    print(f"  rid 0: breakdown attached={'breakdown' in res[0]}")
    print(f"  rid 1: degraded={res[1].get('degraded', False)} "
          f"({res[1].get('fallback_reason', '')[:60]})")
    print(f"  stats: {svc.stats}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
