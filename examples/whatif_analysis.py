"""Paper §V what-if analysis, both worlds:

    PYTHONPATH=src python examples/whatif_analysis.py

HPL: which upgrade moves Frontera — faster fabric or faster memory?
     (the whole grid runs as ONE batched fastsim program; paper found
     2x fabric buys only +2.6%)
TPU: which upgrade moves a MoE train step — 2x ICI, 2x HBM, or 2x MXU?
FT:  should a 3x-slow chip be evicted mid-run?
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.core.predict import whatif_grid
from repro.platforms import get_platform


def main():
    print("== HPL: fabric x memory what-if grid (Frontera, one batch) ==")
    plat = get_platform("frontera")
    cfg = plat.hpl_config()
    base = plat.fastsim()
    grid = whatif_grid(cfg, base, {"link_bw": [1.0, 2.0, 4.0],
                                   "mem_bw": [1.0, 1.25]})
    for row in grid:
        print(f"  link_bw x{row['link_bw']:.2f} mem_bw x{row['mem_bw']:.2f}"
              f": {row['tflops']:.0f} TF ({(row['speedup']-1)*100:+.1f}%)")
    x2 = next(r for r in grid if r["link_bw"] == 2.0 and r["mem_bw"] == 1.0)
    print(f"  2x fabric alone: {(x2['speedup']-1)*100:+.1f}% — paper found "
          f"+2.6%: upgrade not worth it")

    rec = Path("experiments/dryrun/qwen3-moe-235b-a22b__train_4k__16x16.json")
    if rec.exists():
        from repro.core.predict import whatif
        print("== TPU: qwen3-moe-235b train_4k on one v5e pod ==")
        for name, kw in [("2x ICI", dict(link_bw_scale=2.0)),
                         ("2x HBM bw", dict(hbm_bw_scale=2.0)),
                         ("2x MXU peak", dict(peak_scale=2.0))]:
            w = whatif("qwen3-moe-235b-a22b", "train_4k", **kw)
            print(f"  {name:12s}: {w['baseline_s']:.2f}s -> "
                  f"{w['whatif_s']:.2f}s ({w['speedup']:.2f}x)")
        from repro.ft.straggler import simulate_straggler_impact
        print("== FT: one 3x-slow chip (qwen2-0.5b train, DES) ==")
        s = simulate_straggler_impact("qwen2-0.5b", "train_4k",
                                      slowdown=3.0)
        print(f"  step {s['baseline_s']:.3f}s -> {s['straggler_s']:.3f}s "
              f"({s['blowup']:.2f}x) — verdict: {s['verdict']}")
    else:
        print("(TPU sections skipped — run repro.launch.dryrun --all first)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
