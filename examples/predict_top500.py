"""Predict a TOP500 list end to end: parse -> infer -> one batched sweep.

    PYTHONPATH=src python examples/predict_top500.py [path/to/list.csv]

Uses the vendored June-2020-era sample (51 systems) by default.  Shows
the ranked predicted-vs-published Rmax table, the fitted per-fabric
efficiency factors, and one machine's inference provenance — the audit
trail explaining every heuristic that shaped its spec.
"""
import sys

from repro.top500 import (load_sample, parse_top500, predict_fleet,
                          FleetTuning)


def main() -> None:
    rows = (parse_top500(sys.argv[1]).rows if len(sys.argv) > 1
            else load_sample())
    report = predict_fleet(rows,
                           tuning=FleetTuning(max_ranks=256,
                                              panels_cap=2048))

    print(f"{len(rows)} machines, one compiled sweep "
          f"(bucket {report.bucket}, {report.compiles} compile)\n")
    print(f"{'#':>3} {'machine':42s} {'family':10s} "
          f"{'pred TF':>10} {'publ TF':>10} {'err':>7}")
    for pos, e in enumerate(report.ranked(), 1):
        print(f"{pos:3d} {e.platform.name:42.42s} {e.family:10s} "
              f"{e.calibrated_tflops:10.0f} {e.published_tflops:10.0f} "
              f"{e.rel_err:+7.1%}")

    cal = report.calibration
    print(f"\nheld-out median |err|: {cal.heldout_median_abs_err:.1%} "
          f"({cal.n_train} train / {cal.n_test} test)")
    print("family efficiency factors:",
          {k: round(v, 3) for k, v in sorted(cal.factors.items())})

    e = report.ranked()[0]
    print(f"\nprovenance for {e.platform.name}:")
    for key, val in e.platform.provenance:
        print(f"  {key:16s} {val}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
