"""Training launcher.

    python -m repro.launch.train --arch qwen2-0.5b --smoke --steps 50
    python -m repro.launch.train --arch minitron-8b --shape train_4k --dryrun

Full production shapes only *lower/compile* on this CPU container (the
dry-run path); real execution is for reduced configs (--smoke).
"""
from __future__ import annotations

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config, real execution on CPU")
    ap.add_argument("--dryrun", action="store_true",
                    help="lower+compile the production cell instead")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    # reduced-config runs and dry-run compiles both target the host CPU:
    # pinned before jax is imported, so neither this process nor the
    # dry-run child it starts takes (or waits on) a TPU
    os.environ["JAX_PLATFORMS"] = "cpu"

    if args.dryrun:
        import subprocess
        import sys
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch",
               args.arch, "--shape", args.shape]
        if args.multi_pod:
            cmd.append("--multi-pod")
        raise SystemExit(subprocess.call(cmd))

    from repro.configs import get_config, reduced
    from repro.train.loop import train

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    res = train(cfg, steps=args.steps, global_batch=args.global_batch,
                seq_len=args.seq_len, lr=args.lr,
                microbatches=args.microbatches, ckpt_dir=args.ckpt_dir)
    print(f"[train] done: loss {res['first_loss']:.4f} -> "
          f"{res['final_loss']:.4f} (median step "
          f"{res['median_step_s']*1e3:.0f} ms)")


if __name__ == "__main__":
    main()
