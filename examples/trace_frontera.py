"""Trace a Frontera DES run: dump a Chrome trace and summarize it.

    PYTHONPATH=src python examples/trace_frontera.py [--smoke]
        [--out trace_frontera.json] [-N 8192] [--nb 128] [-P 4] [-Q 8]

Runs HPL on Frontera's registry spec (CLX-8280 nodes on the HDR
fat-tree) scaled down to a grid the DES chews through in seconds, with
``trace=True``.  Writes Chrome trace-event JSON — drag it into
https://ui.perfetto.dev (or chrome://tracing) to see one track per rank
with panel_fact / panel_bcast / row_swap / trailing_update phases, the
SimMPI collectives under them, and async slices for in-flight messages —
then prints the per-rank compute/comm/idle breakdown and the critical
path extracted from the recorded happens-before graph.
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.core.apps.hpl import HPLSim
from repro.platforms import get_platform
from repro.trace import (collective_breakdown, critical_path,
                         phase_breakdown, rank_breakdown)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for CI (sub-second run)")
    ap.add_argument("--out", default="trace_frontera.json")
    ap.add_argument("-N", type=int, default=None)
    ap.add_argument("--nb", type=int, default=128)
    ap.add_argument("-P", type=int, default=None)
    ap.add_argument("-Q", type=int, default=None)
    args = ap.parse_args(argv)
    if args.smoke:
        N, P, Q = 1024, 2, 4
    else:
        N = args.N if args.N is not None else 8192
        P = args.P if args.P is not None else 4
        Q = args.Q if args.Q is not None else 8

    plat = get_platform("frontera")
    cfg = plat.hpl_config(N=N, nb=args.nb, P=P, Q=Q)
    print(f"tracing HPL N={cfg.N} nb={cfg.nb} grid={cfg.P}x{cfg.Q} "
          f"on {plat.name!r} ...")
    t0 = time.perf_counter()
    res = HPLSim(cfg, plat, trace=True).run()
    wall = time.perf_counter() - t0
    tr = res.trace
    tr.to_chrome_json(args.out)
    bd = rank_breakdown(tr)              # each analysis pass runs once
    cp = critical_path(tr)

    print(f"  simulated {res.time_s*1e3:.2f} ms ({res.gflops:.0f} GF) in "
          f"{wall:.2f}s wall, {res.events} events")
    print(f"  wrote {args.out}: {len(tr.spans)} spans, {len(tr.msgs)} msgs "
          f"-> open in https://ui.perfetto.dev")

    print("\n  where simulated time goes (mean over ranks):")
    for k in ("compute", "comm", "idle"):
        frac = sum(acc[k] for acc in bd.values()) / len(bd) / res.time_s
        print(f"    {k:8s} {frac*100:5.1f}%")
    print("  phases (rank-seconds):")
    for name, sec in sorted(phase_breakdown(tr).items(),
                            key=lambda kv: -kv[1]):
        print(f"    {name:16s} {sec*1e3:8.2f} ms")
    print("  collectives:")
    for name, acc in sorted(collective_breakdown(tr).items(),
                            key=lambda kv: -kv[1]["seconds"]):
        print(f"    {name:16s} {acc['seconds']*1e3:8.2f} ms over "
              f"{acc['calls']} calls")

    print(f"\n  critical path: {cp.length_s*1e3:.2f} ms of "
          f"{cp.makespan_s*1e3:.2f} ms makespan "
          f"({cp.coverage*100:.0f}% explained, {len(cp.spans)} spans)")
    for cat, sec in sorted(cp.by_cat.items(), key=lambda kv: -kv[1]):
        print(f"    on-path {cat:8s} {sec*1e3:8.2f} ms")

    worst = max(bd.items(), key=lambda kv: kv[1]["comm"])
    print(f"  most comm-bound rank: {worst[0]} "
          f"({worst[1]['comm']/worst[1]['total']*100:.0f}% comm)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
