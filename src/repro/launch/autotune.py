"""Simulation-driven sharding selection (beyond-paper).

The paper's §V pitch is deployment planning without touching the cluster.
Applied to our own framework: for a given (arch × shape × mesh) cell,
*dry-run every candidate sharding scheme* (tp / sp / dp + remat policies),
analyze each compiled artifact, and pick the scheme with the lowest
roofline bound — the simulator chooses the parallelism config.

    PYTHONPATH=src python -m repro.launch.autotune --arch mamba2-780m \
        --shape train_4k

Each candidate costs one lower+compile (~10 s on this container); results
land in experiments/autotune/ and the winner is printed with its full
term breakdown.
"""
import os
# a compile rehearsal on 512 host CPU devices: pinned to the CPU before
# jax is imported, so it never takes a TPU on a machine that has one
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

import argparse
import json
from pathlib import Path


def candidates_for(cfg, shape):
    """Candidate (tag, overrides) list — legal schemes only."""
    from repro.sharding.specs import scheme_for
    base_scheme = scheme_for(cfg, 16)
    cands = [("default", {})]
    for scheme in ("tp", "sp", "dp"):
        if scheme == base_scheme:
            continue
        if scheme == "tp" and not (cfg.n_kv_heads % 16 == 0
                                   or (cfg.n_heads // cfg.n_kv_heads) % 16
                                   == 0 or cfg.family == "ssm"):
            continue
        cands.append((f"scheme_{scheme}", {"force_scheme": scheme}))
    if shape.kind == "train" and cfg.remat != "dots_nb":
        cands.append(("dots_nb", {"remat": "dots_nb"}))
    if shape.kind == "train" and cfg.remat != "full":
        cands.append(("remat_full", {"remat": "full"}))
    return cands


def autotune(arch: str, shape_name: str, multi_pod: bool = False,
             out_dir: str = "experiments/autotune"):
    from repro.configs import get_config, get_shape
    from repro.launch.dryrun import run_cell

    cfg = get_config(arch)
    shape = get_shape(shape_name)
    results = []
    for tag, overrides in candidates_for(cfg, shape):
        try:
            rec = run_cell(arch, shape_name, multi_pod, Path(out_dir),
                           overrides=overrides or None,
                           tag=f"auto_{tag}")
        except Exception as e:   # a candidate failing is information
            results.append({"tag": tag, "ok": False, "error": str(e)[:200]})
            continue
        k = rec.get("roofline_kernel_adjusted") or rec["roofline"]
        # feasibility: exact persistent (state/params+cache) bytes per
        # device must leave headroom for activations (XLA:CPU temp_size is
        # not a TPU memory plan — EXPERIMENTS.md §Limitations)
        hbm_bytes = rec.get("persistent_bytes_per_device", 0)
        fits = hbm_bytes <= 0.8 * 16e9
        results.append({"tag": tag, "ok": True, "fits_hbm": fits,
                        "hbm_gb": hbm_bytes / 1e9,
                        "bound_s": k["bound_s"],
                        "dominant": k["dominant"],
                        "compute_s": k["compute_s"],
                        "memory_s": k["memory_s"],
                        "collective_s": k["collective_s"],
                        "mfu": k.get("mfu_at_bound", 0.0)})
    ok = [r for r in results if r.get("ok") and r.get("fits_hbm", True)]
    ok.sort(key=lambda r: r["bound_s"])
    print(f"\n[autotune] {arch} x {shape_name} "
          f"({'2x16x16' if multi_pod else '16x16'}):")
    for r in ok:
        mark = " <== winner" if r is ok[0] else ""
        print(f"  {r['tag']:14s} bound={r['bound_s']:8.3f}s "
              f"dom={r['dominant']:10s} mfu={r['mfu']:.3f} "
              f"hbm={r['hbm_gb']:.1f}GB{mark}")
    for r in results:
        if r.get("ok") and not r.get("fits_hbm", True):
            print(f"  {r['tag']:14s} INFEASIBLE: persistent state "
                  f"{r['hbm_gb']:.1f} GB > 80% of 16 GB HBM "
                  f"(bound would be {r['bound_s']:.3f}s)")
        elif not r.get("ok"):
            print(f"  {r['tag']:14s} FAILED: {r['error']}")
    summary = Path(out_dir) / f"{arch}__{shape_name}__summary.json"
    summary.parent.mkdir(parents=True, exist_ok=True)
    summary.write_text(json.dumps(results, indent=1))
    return ok[0] if ok else None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()
    autotune(args.arch, args.shape, args.multi_pod)


if __name__ == "__main__":
    main()
