#!/usr/bin/env python3
"""The control of a cell's check: the reference in float32, one precision
below the float64 the configurations state, put in the program's place.

    python3 bench/control.py --workload <cell> --seeds 11 12 13

For each seed it makes a run of the cell as ``run.py`` makes one, with
one wave in the window, but every wave (the warm-up's too) is answered
by the float32 reference instead of the service.  The harness then
checks that window as it checks any: a sound limit reads each control
run as ``correct: false``.  Each run's result line is printed as a run
prints it.
"""
from __future__ import annotations

import argparse
import sys

import numpy as np

from harness import ROOT, process_pool, run_cell


def float32_in_place(mapper=map):
    """``place`` for ``run_cell``: each wave goes to the entry's reference
    in float32, untouched by the program."""
    def place(entry):
        entry.build = lambda wave: wave
        entry.serve = lambda wave: entry.reference_answers(
            wave, np.float32, mapper)
    return place


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--workers", type=int, default=8)
    args = ap.parse_args(argv)
    rc = 0
    pool = process_pool(args.workers)
    try:
        for seed in args.seeds:
            rc = max(rc, run_cell(
                args.workload, seed, 0.0, False, root=ROOT, chip=False,
                persistent_cache=False, workers=args.workers,
                place=float32_in_place(pool.map if pool else map)))
    finally:
        if pool is not None:
            pool.shutdown(wait=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
