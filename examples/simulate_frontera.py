"""Reproduce paper Table II: predict Frontera + PupMaya HPL Rmax from
their registry specs, on this laptop-class container, in seconds.

    PYTHONPATH=src python examples/simulate_frontera.py

Every machine number (node peak, fabric, grid, Nmax, reported Rmax)
comes from ``repro.platforms`` — change the spec, re-run the prediction.
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.core.fastsim import simulate_hpl_fast
from repro.platforms import get_platform

SYSTEMS = [("frontera", "Frontera (#5)", "4.8 h"),
           ("pupmaya", "PupMaya (#25)", "1.7 h")]


def main():
    print(f"{'system':15s} {'reported':>9s} {'paper sim':>9s} "
          f"{'our sim':>9s} {'our err':>8s} {'exec':>7s} {'sim wall':>9s}")
    for name, label, paper_wall in SYSTEMS:
        plat = get_platform(name)
        cfg = plat.hpl_config()
        prm = plat.fastsim()
        reported = plat.scale.reported_tflops
        paper_pred = plat.scale.paper_pred_tflops
        t0 = time.perf_counter()
        res = simulate_hpl_fast(cfg, prm)
        wall = time.perf_counter() - t0
        err = (res["tflops"] - reported) / reported * 100
        print(f"{label:15s} {reported:8.0f}T {paper_pred:8.0f}T "
              f"{res['tflops']:8.0f}T {err:+7.1f}% "
              f"{res['time_s']/3600:6.2f}h {wall:8.1f}s"
              f"   (paper sim wall: {paper_wall})")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
