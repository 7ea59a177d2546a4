import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above MUST run before any jax import — jax locks the platform
and the device count on first init.  The dry run is a compile rehearsal
on 512 host CPU devices; pinned to the CPU it never takes a TPU on a
machine that has one (and the per-cell children of ``--all`` inherit the
pin, so they never fight over it).  This proves the distribution config
is coherent without real hardware: a sharding mismatch, compile-time
OOM, or an unsupported collective is a bug in the framework, surfaced
here.

Usage:
    python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
    python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k --multi-pod
    python -m repro.launch.dryrun --all            # full sweep (subprocess per cell)
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path


def _cell_out(out_dir: Path, arch: str, shape: str, multi_pod: bool,
              tag: str = "") -> Path:
    mesh = "2x16x16" if multi_pod else "16x16"
    suffix = f"__{tag}" if tag else ""
    return out_dir / f"{arch}__{shape}__{mesh}{suffix}.json"


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             overrides: dict | None = None, tag: str = "") -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, get_shape, shape_applicable
    from repro.launch.mesh import make_production_mesh
    from repro.models import build_model
    from repro.models.api import (abstract_cache, abstract_params,
                                  abstract_state, input_specs,
                                  input_logical_specs)
    from repro.roofline.analysis import roofline_terms
    from repro.roofline.hlo_parse import (analyze, pattern_traffic,
                                          score_matcher, chunk_matcher)
    from repro.sharding.specs import (make_rules, tree_shardings, use_rules,
                                      resolve)
    from repro.train.step import make_train_step, state_specs

    cfg = get_config(arch)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    shape = get_shape(shape_name)
    if not shape_applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": "long_500k requires sub-quadratic attention "
                          "(see DESIGN.md §5)"}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    mode = "train" if shape.kind == "train" else "serve"
    rules = make_rules(cfg, multi_pod=multi_pod, mode=mode,
                       global_batch=shape.global_batch)
    model = build_model(cfg)
    t0 = time.time()

    def bf16_params(p):
        return jax.tree.map(
            lambda s: (jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
                       if jnp.issubdtype(s.dtype, jnp.floating) else s), p)

    def sharded_bytes(abs_tree, sh_tree):
        """Exact persistent bytes per device (state / params+cache) from the
        shardings — the HBM-fit number (XLA:CPU temp_size is not a TPU
        memory plan; see EXPERIMENTS.md §Limitations)."""
        import numpy as np
        leaves = zip(jax.tree.leaves(abs_tree), jax.tree.leaves(sh_tree))
        total = 0
        for a, sh in leaves:
            shard = sh.shard_shape(a.shape)
            total += int(np.prod(shard)) * a.dtype.itemsize
        return total

    with mesh, use_rules(rules, mesh):
        in_logical = input_logical_specs(cfg, shape)
        batch_sh = {k: jax.sharding.NamedSharding(mesh, resolve(v, rules))
                    for k, v in in_logical.items()}
        batch_abs = input_specs(cfg, shape)

        if shape.kind == "train":
            step_fn, _ = make_train_step(cfg)
            sspec = state_specs(cfg, model)
            state_abs = abstract_state(cfg)
            state_sh = tree_shardings(sspec, mesh, rules, state_abs)
            persistent_bytes = sharded_bytes(state_abs, state_sh)
            jitted = jax.jit(step_fn,
                             in_shardings=(state_sh, batch_sh),
                             out_shardings=(state_sh, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_abs, batch_abs)
        elif shape.kind == "prefill":
            params_abs = bf16_params(abstract_params(cfg))
            params_sh = tree_shardings(model.param_specs(), mesh, rules,
                                       params_abs)
            cache_abs = abstract_cache(cfg, shape)
            cache_sh = tree_shardings(model.cache_specs(), mesh, rules,
                                      cache_abs)

            def prefill_fn(params, batch):
                return model.prefill(params, batch, max_len=shape.seq_len)
            persistent_bytes = (sharded_bytes(params_abs, params_sh)
                                + sharded_bytes(cache_abs, cache_sh))
            jitted = jax.jit(prefill_fn,
                             in_shardings=(params_sh, batch_sh),
                             out_shardings=(cache_sh, None))
            lowered = jitted.lower(params_abs, batch_abs)
        else:  # decode
            params_abs = bf16_params(abstract_params(cfg))
            params_sh = tree_shardings(model.param_specs(), mesh, rules,
                                       params_abs)
            cache_abs = abstract_cache(cfg, shape)
            cache_sh = tree_shardings(model.cache_specs(), mesh, rules,
                                      cache_abs)
            tok_sh = batch_sh["tokens"]
            persistent_bytes = (sharded_bytes(params_abs, params_sh)
                                + sharded_bytes(cache_abs, cache_sh))
            jitted = jax.jit(model.decode,
                             in_shardings=(params_sh, cache_sh, tok_sh),
                             out_shardings=(cache_sh, None),
                             donate_argnums=(1,))
            lowered = jitted.lower(params_abs, cache_abs,
                                   batch_abs["tokens"])

        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            v = getattr(ma, k, None)
            if v is not None:
                mem[k] = int(v)
        print("memory_analysis:", mem)
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}

    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        cost = {k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and k in
                ("flops", "bytes accessed", "transcendentals",
                 "utilization operand 0 {}", "optimal_seconds")}
        print("cost_analysis: flops=%.4g bytes=%.4g" %
              (cost.get("flops", 0), cost.get("bytes accessed", 0)))
    except Exception as e:  # pragma: no cover
        cost = {"error": str(e)}

    hlo = compiled.as_text()
    hh = analyze(hlo)   # while-loop-aware flops/bytes/collectives (per device)
    coll_by_op = hh["collectives"]
    per_dev_coll = hh["coll_wire_bytes"]

    terms = roofline_terms(
        per_device_flops=hh["flops"],
        per_device_bytes=hh["bytes"],
        per_device_coll_bytes=per_dev_coll,
        chips=chips, cfg=cfg, shape=shape)
    print("hlo_analyze: flops=%.4g bytes=%.4g coll=%.4g" %
          (hh["flops"], hh["bytes"], per_dev_coll))

    # kernel-adjusted roofline: measured traffic of score-/chunk-shaped
    # tiles (which the Pallas flash/SSD kernels keep in VMEM) is removed;
    # causally-skippable score dot flops are halved (kernels/flash_attention
    # skips above-diagonal blocks with @pl.when).
    kadj = None
    if shape.kind != "decode":
        sc_bytes = sc_dots = 0.0
        if not cfg.attention_free:
            sc = pattern_traffic(hlo, score_matcher(
                min(shape.seq_len, 32768), cfg.attn_block))
            sc_bytes += sc["bytes"]
            sc_dots += sc["dot_flops"]
        if cfg.ssm is not None and cfg.attention_free:
            # pure-SSM only: on hybrids the chunk matcher can overlap the
            # score matcher (double-count) — stay conservative
            ck = pattern_traffic(hlo, chunk_matcher(cfg.ssm.chunk_size))
            sc_bytes += ck["bytes"]
            sc_dots += ck["dot_flops"] * 0.0   # SSD chunk dots are dense
        adj_flops = hh["flops"] - 0.5 * sc_dots
        adj_bytes = max(hh["bytes"] - sc_bytes, 0.0)
        kadj = roofline_terms(
            per_device_flops=adj_flops, per_device_bytes=adj_bytes,
            per_device_coll_bytes=per_dev_coll, chips=chips,
            cfg=cfg, shape=shape)
        kadj["removed_tile_bytes"] = sc_bytes
        kadj["halved_score_dot_flops"] = sc_dots
        print("kernel-adjusted: flops=%.4g bytes=%.4g -> bound=%.4gs" %
              (adj_flops, adj_bytes, kadj["bound_s"]))

    rec = {
        "arch": arch, "shape": shape_name, "tag": tag,
        "overrides": {k: str(v) for k, v in (overrides or {}).items()},
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "kind": shape.kind,
        "compile_s": t_compile,
        "memory_analysis": mem, "cost_analysis": cost,
        "persistent_bytes_per_device": persistent_bytes,
        "collectives": coll_by_op, "roofline": terms,
        "roofline_kernel_adjusted": kadj,
        "scheme": rules.get("tp") and "tp" or "sp",
        "ok": True,
    }
    out_path = _cell_out(out_dir, arch, shape_name, multi_pod, tag)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    out_path.write_text(json.dumps(rec, indent=1))
    print(f"[dryrun] {arch} x {shape_name} x {rec['mesh']}: "
          f"compile {t_compile:.1f}s, dominant={terms['dominant']}, "
          f"bound={terms['bound_s']:.4g}s")
    return rec


def sweep(out_dir: Path, multi_pod_too: bool = True, force: bool = False):
    from repro.configs import SHAPES, list_archs, get_config, shape_applicable
    cells = []
    for arch in list_archs():
        for shape in SHAPES:
            for mp in ([False, True] if multi_pod_too else [False]):
                cells.append((arch, shape, mp))
    done = failed = skipped = 0
    for arch, shape, mp in cells:
        out = _cell_out(out_dir, arch, shape, mp)
        if out.exists() and not force:
            prev = json.loads(out.read_text())
            if prev.get("ok") or prev.get("skipped"):
                done += 1
                continue
        if not shape_applicable(get_config(arch), __import__(
                "repro.configs", fromlist=["SHAPES"]).SHAPES[shape]):
            out.parent.mkdir(parents=True, exist_ok=True)
            out.write_text(json.dumps(
                {"arch": arch, "shape": shape, "skipped": True,
                 "mesh": "2x16x16" if mp else "16x16",
                 "reason": "long_500k needs sub-quadratic attention"}))
            skipped += 1
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
               "--shape", shape, "--out", str(out_dir)]
        if mp:
            cmd.append("--multi-pod")
        print(f"[sweep] {arch} x {shape} x "
              f"{'2x16x16' if mp else '16x16'}", flush=True)
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=7200)
        if r.returncode != 0:
            failed += 1
            out.parent.mkdir(parents=True, exist_ok=True)
            out.write_text(json.dumps(
                {"arch": arch, "shape": shape, "ok": False,
                 "mesh": "2x16x16" if mp else "16x16",
                 "error": r.stdout[-2000:] + r.stderr[-4000:]}))
            print(f"[sweep] FAILED {arch} x {shape}:\n{r.stderr[-1500:]}",
                  flush=True)
        else:
            done += 1
    print(f"[sweep] done={done} failed={failed} skipped={skipped}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="", help="suffix for the output record")
    ap.add_argument("--set", action="append", default=[],
                    help="config override key=value (perf hillclimb)")
    args = ap.parse_args()
    overrides = {}
    for kv in getattr(args, "set"):
        k, v = kv.split("=", 1)
        try:
            v = int(v)
        except ValueError:
            try:
                v = float(v)
            except ValueError:
                pass
        overrides[k] = v
    out_dir = Path(args.out)
    if args.all:
        sweep(out_dir, force=args.force)
        return
    try:
        run_cell(args.arch, args.shape, args.multi_pod, out_dir,
                 overrides=overrides or None, tag=args.tag)
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
