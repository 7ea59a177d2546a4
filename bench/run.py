#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this machine holds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the checkout's root.  The
last line of standard output is the run's result as one JSON object;
the last lines of standard error give each number the check compared,
beside its limit.  Without an accelerator, or with fewer chips than the
cell asks for, it prints no result and exits with code 3.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from harness import run_cell
    return run_cell(args.workload, args.seed, args.seconds,
                    bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
