"""One platform spec, two applications: predict HPL Rmax *and* LM
train-step time from the same registry entry.

    PYTHONPATH=src python examples/predict_workloads.py
    PYTHONPATH=src python examples/predict_workloads.py --platform syn-torus-fugaku-4k

This is the workload layer's point (DESIGN.md §15): the `tpu-v5e-pod`
entry carries everything both predictors need — chip peak/HBM, ICI
geometry and bandwidths, MPI-stack knobs, the published HPL run — so
"what does this machine do on HPL" and "what does it do training an LM"
are the same one-liner with a different workload name.
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.platforms import get_platform
from repro.workloads import get_workload


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--platform", default="tpu-v5e-pod")
    args = ap.parse_args()
    plat = get_platform(args.platform)
    print(f"[workloads] platform {plat.name}: "
          f"{plat.scale.n_ranks} ranks, {plat.fabric.kind} fabric, "
          f"{plat.node.peak_flops/1e12:.0f} TF/chip")

    hpl = get_workload("hpl").predict(plat)
    print(f"[workloads] hpl         : {hpl['tflops']:10.1f} TF "
          f"(exec {hpl['time_s']:.1f} s on the published run geometry)")

    lm = get_workload("transformer").predict(plat)
    print(f"[workloads] transformer : {lm['step_s']*1e3:10.3f} ms/step "
          f"({lm['tokens_per_s']:.3g} tok/s, mfu {lm['mfu']:.3f})")

    # the same what-if, both workloads: double the interconnect
    from repro.core.predict import whatif_grid
    for name in ("hpl", "transformer"):
        row = whatif_grid(get_workload(name), plat,
                          {"link_bw": [2.0]})[0]
        print(f"[workloads] 2x link_bw on {name:11s}: "
              f"{row['speedup']:.3f}x speedup")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
