"""Longitudinal TOP500 drift study as one declarative campaign.

    PYTHONPATH=src python examples/campaign_top500_drift.py [--smoke]
        [--limit N] [--journal runs.ndjson] [--markdown]

Runs the campaign layer's first customer end to end: both vendored
TOP500 sample editions (June-2020-era and Nov-2020-era) are ingested,
a Platform is inferred per machine, each edition's fleet is predicted
as ONE forced-bucket batched sweep with per-fabric calibration, every
machine's prediction is journaled as one NDJSON line, and the report
renders

  * the per-edition ranked predicted-vs-published table,
  * per-machine prediction drift between the editions (machines
    matched by their edition-stable slug — Fugaku's expansion and
    Selene's doubling show up as predicted drift tracking published
    drift), and
  * per-fabric calibration-factor drift (did the model's systematic
    bias move between lists?).

The same study is available from the CLI:

    python -m repro.campaign run --edition-study 2020_06 2020_11
"""
import argparse
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.campaign import (campaign_report, dispatch_counts,
                            edition_study_spec, render_markdown,
                            render_text, run_campaign)
from repro.top500 import FleetTuning


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small proxy grids + top-12 rows per edition")
    ap.add_argument("--limit", type=int, default=0,
                    help="top-N rows per edition (0 = whole sample)")
    ap.add_argument("--journal", default=None,
                    help="append one NDJSON line per machine")
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args(argv)

    limit = args.limit or (12 if args.smoke else 0)
    tuning = (FleetTuning(max_ranks=256, panels_cap=2048)
              if args.smoke else None)

    spec = edition_study_spec(["2020_06", "2020_11"], limit=limit)
    result = run_campaign(spec, journal=args.journal, tuning=tuning)

    report = campaign_report(result.records)
    render = render_markdown if args.markdown else render_text
    print(render(report), end="")

    meta = result.summary["meta"]
    d = meta["dispatches"]
    print(f"\n[{meta['runs']} machines across 2 editions in "
          f"{meta['wall_s']:.1f}s; {d['fastsim_dispatches']} batched "
          f"sweep dispatch(es), {d['fastsim_compiles']} fresh "
          f"compile(s)"
          + (f"; journal -> {args.journal}" if args.journal else "")
          + "]", file=sys.stderr)
    return 0


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
