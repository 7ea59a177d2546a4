"""Quickstart: the three faces of the framework in ~a minute.

    PYTHONPATH=src python examples/quickstart.py

1. Simulate HPL on a small cluster (the paper's case study) with the DES
   and the fast vectorized simulator.
2. Predict a TOP500 system (Frontera) from public specs.
3. Predict a TPU transformer cell from its compiled dry-run record (if
   experiments/dryrun exists).
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import dataclasses

from repro.core.apps.hpl import HPLSim
from repro.core.fastsim import simulate_hpl_fast
from repro.platforms import get_platform


def main():
    print("== 1. small-cluster HPL (DES + fastsim) ==")
    plat = get_platform("bdw-local")        # paper Table I machine
    cfg = plat.hpl_config()
    res = HPLSim(cfg, plat).run()
    print(f"  DES: {res.gflops:.0f} GF in {res.time_s:.3f}s simulated "
          f"({res.events} events)")
    fast = simulate_hpl_fast(
        cfg, dataclasses.replace(plat.fastsim(), lookahead=0.0))
    print(f"  fastsim: {fast['gflops']:.0f} GF "
          f"(agreement {abs(1 - fast['time_s']/res.time_s)*100:.1f}%)")

    print("== 2. Frontera (TOP500 #5) prediction ==")
    frontera = get_platform("frontera")
    reported = frontera.scale.reported_tflops
    t0 = time.perf_counter()
    fast = simulate_hpl_fast(frontera.hpl_config(), frontera.fastsim())
    print(f"  predicted {fast['tflops']:.0f} TF vs {reported:,.0f} TF "
          f"reported ({(fast['tflops']-reported)/reported*100:+.1f}%), "
          f"simulated in {time.perf_counter()-t0:.1f}s "
          f"(paper's SystemC: 4.8 h)")

    rec = Path("experiments/dryrun/qwen2-0.5b__train_4k__16x16.json")
    if rec.exists():
        print("== 3. TPU cell prediction (qwen2-0.5b train_4k, 256 chips) ==")
        from repro.core.predict import predict_cell
        p = predict_cell("qwen2-0.5b", "train_4k")
        print(f"  step {p.step_s*1e3:.0f} ms  (compute {p.compute_s*1e3:.0f}"
              f" / memory {p.memory_s*1e3:.0f}"
              f" / collective {p.collective_s*1e3:.0f} ms)")
    else:
        print("== 3. (skipped — run `python -m repro.launch.dryrun --all`) ==")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
