"""Batched serving driver + simulator-predicted vs measured throughput —
the paper's methodology (predict performance, then check against a real
run) applied to our own serving engine.

    PYTHONPATH=src python examples/serve_batch.py --requests 8
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.models import build_model
from repro.serve.engine import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    args = ap.parse_args()

    cfg = reduced(get_config(args.arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, batch_slots=args.batch_slots,
                      max_len=args.prompt_len + args.max_new + 1)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(
                0, cfg.vocab_size, args.prompt_len).astype(np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]

    # simulator prediction: per-decode-step flops at measured CPU rate
    from repro.core.calibrate import calibrate
    prof = calibrate(quick=True)
    flops_per_tok = 2.0 * cfg.n_active_params() * args.batch_slots
    pred_step = flops_per_tok / prof.dgemm.eff_flops
    n_steps = args.requests // args.batch_slots * args.max_new
    pred_total = n_steps * pred_step

    t0 = time.perf_counter()
    results = eng.run(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(v) for v in results.values())
    print(f"[serve] {len(results)} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s)")
    if pred_total < 0.05 * dt:
        print(f"[serve] simulator: decode compute is {pred_total*1e3:.2f} ms "
              f"— this reduced model is dispatch-overhead-bound on CPU "
              f"({dt:.2f}s measured), exactly what the prediction says: "
              f"batch harder or serve a bigger model")
    else:
        print(f"[serve] simulator predicted decode-compute {pred_total:.2f}s "
              f"vs measured {dt:.2f}s")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
