"""End-to-end training driver: train a language model on the synthetic
pipeline with checkpoint/restart and straggler monitoring.

    PYTHONPATH=src python examples/train_lm.py                  # ~20M, fast
    PYTHONPATH=src python examples/train_lm.py --preset 100m --steps 300

The 100m preset is a ~108M-parameter qwen2-family model (d=768, L=10,
vocab 50257) — "train a ~100M model for a few hundred steps" on CPU.

After training, the same model dims are fed through the transformer
*workload* (``repro.workloads``) to predict what one train step would
cost on an accelerator platform (``--platform``, default tpu-v5e-pod).
Every chip/ICI number comes from the platform registry — nothing is
hardcoded here, and the run fails loudly if the legacy constants drift
from the spec.
"""
import argparse
import dataclasses
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.configs import ModelConfig
from repro.train.loop import train

PRESETS = {
    "20m": dict(num_layers=6, d_model=384, n_heads=6, n_kv_heads=2,
                d_ff=1536, vocab_size=16384, head_dim=64),
    "100m": dict(num_layers=10, d_model=768, n_heads=12, n_kv_heads=4,
                 d_ff=3072, vocab_size=50257, head_dim=64),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="20m", choices=sorted(PRESETS))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--platform", default="tpu-v5e-pod",
                    help="registry platform for the step-time prediction")
    args = ap.parse_args()

    cfg = ModelConfig(name=f"lm-{args.preset}", family="dense",
                      qkv_bias=True, tie_embeddings=True, dtype="float32",
                      optimizer="adafactor", **PRESETS[args.preset])
    n = cfg.n_params()
    print(f"[example] {cfg.name}: ~{n/1e6:.0f}M params, "
          f"{args.steps} steps x {args.batch}x{args.seq} tokens")
    res = train(cfg, steps=args.steps, global_batch=args.batch,
                seq_len=args.seq, lr=args.lr, ckpt_dir=args.ckpt_dir,
                ckpt_every=50, log_every=10)
    losses = res["losses"]
    w = min(10, max(len(losses) // 4, 1))
    head = sum(losses[:w]) / w
    tail = sum(losses[-w:]) / w
    print(f"[example] loss {head:.3f} -> {tail:.3f} "
          f"(window-{w} means); median step "
          f"{res['median_step_s']*1e3:.0f} ms; "
          f"checkpoints in {args.ckpt_dir}")
    # single-step losses are noisy at batch 1: compare windowed means
    assert tail < head + 0.05, "loss must not increase (windowed)"

    # what would this step cost on real accelerators?  Same model dims
    # through the workload layer, chip/ICI numbers from the registry.
    from repro.core.simxla import assert_registry_consistent
    from repro.platforms import get_platform
    from repro.workloads import get_workload

    plat = get_platform(args.platform)
    if args.platform == "tpu-v5e-pod":
        assert_registry_consistent(plat)
    wl = get_workload("transformer", num_layers=cfg.num_layers,
                      d_model=cfg.d_model, d_ff=cfg.d_ff,
                      vocab=cfg.vocab_size, seq_len=args.seq,
                      batch_per_replica=args.batch)
    pred = wl.predict(plat)
    print(f"[example] predicted step on {plat.name}: "
          f"{pred['step_s']*1e3:.3f} ms "
          f"({pred['tokens_per_s']:.3g} tok/s, mfu={pred['mfu']:.3f}; "
          f"peak {plat.node.peak_flops/1e12:.0f} TF/chip from the spec)")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
