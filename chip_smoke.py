#!/usr/bin/env python3
"""Smoke run of the prediction service on a TPU, at full registry scale.

    python3 chip_smoke.py              # one chip: every phase below
    python3 chip_smoke.py --chips 4    # four chips: the sharded what-if
                                       # wave against the unsharded one

One process does everything, the CPU reference included, so it holds
the chip alone.  It refuses to run unless JAX's first device is a TPU.
The phases go through the entry points a user calls:

  1. a ``PredictionService`` what-if wave: Frontera's published HPL run
     (N=9,282,848, nb=384, 88x91) under link_bw x {0.5, 1, 2} and
     gemm_eff x {0.9, 1} — one geometry, so one ``params``-mode sweep;
  2. a mixed wave, Frontera and PupMaya at full scale — two geometries,
     so one forced-bucket ``batch``-mode sweep;
  3. the TOP500 fleet sweep over the vendored June-2020 sample;
  4. one transformer train-step request on ``tpu-v5e-pod`` (stepsim);
  5. the verify anchor, ``simulate_hpl_fast`` on a 4x4 grid;
  6. waves 1, 2 and 4 again, which must trace nothing new.

Every fastsim and stepsim result is then recomputed by the same jitted
programs on the host CPU device (``jax.default_device``) and compared.
Any failed check exits non-zero.  The last line of output is the JSON
object ``{"ok": true, "device": {...}}`` and is printed only on success.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: largest relative difference admitted between a result computed on the
#: chip and the same program on the host CPU (both float64)
CHIP_CPU_RTOL = 1e-9
#: the verify anchor: simulate_hpl_fast(N=4096, nb=128, 4x4) on the
#: paper's local Broadwell node, in seconds (7 significant digits)
ANCHOR_S = 0.0585383
#: hardware what-ifs on Frontera's geometry
LINK_SCALES = (0.5, 1.0, 2.0)
GEMM_SCALES = (0.9, 1.0)
#: Frontera's prediction must land within this of its published Rmax
PUBLISHED_RTOL = 0.05


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Scale of a smoke run.  ``None`` overrides mean the registry's own
    published runs and the whole vendored TOP500 sample."""
    frontera: Optional[Dict[str, int]] = None     # HPL spec overrides
    pupmaya: Optional[Dict[str, int]] = None
    fleet_rows: Optional[int] = None
    fleet_kw: Optional[Dict[str, object]] = None  # predict_fleet keywords

    @property
    def full(self) -> bool:
        return self.frontera is None


FULL = Sizes()


def require_tpu(chips: int):
    """JAX's devices when the first is a TPU and there are ``chips`` of
    them; otherwise exit non-zero naming what was found."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX found platform "
                         f"{devs[0].platform!r} ({devs[0].device_kind})")
    if len(devs) != chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but JAX found "
                         f"{len(devs)} TPU devices")
    return devs


class CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        self.hits = 0
        self.misses = 0

    def __call__(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ----------------------------------------------------------- the traffic
def whatif_requests(sizes: Sizes):
    """Frontera under each (link_bw, gemm_eff) scale, as platform specs:
    every request shares Frontera's geometry."""
    from repro.platforms import get_platform
    from repro.serve import WorkloadRequest
    base = get_platform("frontera")
    reqs = []
    for lx in LINK_SCALES:
        for gx in GEMM_SCALES:
            plat = dataclasses.replace(
                base, name=f"frontera@link_bw*{lx},gemm_eff*{gx}",
                fabric=dataclasses.replace(
                    base.fabric, link_bw=base.fabric.link_bw * lx),
                node=dataclasses.replace(
                    base.node,
                    gemm_efficiency=base.node.gemm_efficiency * gx))
            reqs.append(WorkloadRequest(rid=len(reqs), workload="hpl",
                                        platform=plat,
                                        params=dict(sizes.frontera or {})))
    return reqs


def mixed_requests(sizes: Sizes):
    from repro.serve import WorkloadRequest
    return [WorkloadRequest(rid=0, workload="hpl", platform="frontera",
                            params=dict(sizes.frontera or {})),
            WorkloadRequest(rid=1, workload="hpl", platform="pupmaya",
                            params=dict(sizes.pupmaya or {}))]


def step_requests(sizes: Sizes):
    from repro.serve import WorkloadRequest
    return [WorkloadRequest(rid=0, workload="transformer",
                            platform="tpu-v5e-pod")]


def serve(reqs) -> List[float]:
    """One wave through a fresh ``PredictionService``; times in rid
    order.  The results are host floats, so the device work is done."""
    from repro.serve import PredictionService
    out = PredictionService().predict_batch(reqs)
    return [out[r.rid]["time_s"] for r in reqs]


def fleet(sizes: Sizes) -> List[float]:
    from repro.top500 import load_sample, predict_fleet
    rows = load_sample()[:sizes.fleet_rows]
    report = predict_fleet(rows, **(sizes.fleet_kw or {}))
    return [e.predicted_tflops for e in report.entries]


def anchor() -> List[float]:
    from repro.core.apps.hpl import HPLConfig
    from repro.core.fastsim import FastSimParams, simulate_hpl_fast
    from repro.core.hardware.node import local_node
    prm = FastSimParams.from_node(local_node(), link_bw=100e9 / 8)
    return [simulate_hpl_fast(HPLConfig(N=4096, nb=128, P=4, Q=4),
                              prm)["time_s"]]


def phases(sizes: Sizes) -> Dict[str, Callable[[], List[float]]]:
    """name -> zero-argument callable returning that phase's times."""
    return {
        "what-if wave (params)": lambda: serve(whatif_requests(sizes)),
        "mixed wave (batch)": lambda: serve(mixed_requests(sizes)),
        "top500 fleet (batch)": lambda: fleet(sizes),
        "transformer step (stepsim)": lambda: serve(step_requests(sizes)),
        "verify anchor (single)": anchor,
    }


# ---------------------------------------------------------------- checks
def trace_total() -> int:
    from repro.core import fastsim
    from repro import workloads
    return fastsim.trace_count() + workloads.trace_count()


def rel_diff(a: List[float], b: List[float]) -> float:
    return max(abs(x - y) / abs(y) for x, y in zip(a, b))


def _chip_phases(ph, cache: CacheEvents, failures: List[str],
                 log) -> Dict[str, List[float]]:
    """Run each phase cold, then warm, on the default device; then the
    served waves once more, which must trace nothing new."""
    chip: Dict[str, List[float]] = {}
    for name, fn in ph.items():
        t0, h0, m0 = trace_total(), cache.hits, cache.misses
        w0 = time.perf_counter()
        chip[name] = fn()
        cold = time.perf_counter() - w0
        w0 = time.perf_counter()
        again = fn()
        warm = time.perf_counter() - w0
        log(f"{name}: cold {cold:.3f} s, warm {warm:.3f} s, compile "
            f"~{cold - warm:.3f} s; traces +{trace_total() - t0}; "
            f"compile cache hits {cache.hits - h0}, misses "
            f"{cache.misses - m0}")
        if again != chip[name]:
            failures.append(f"{name}: a repeated run gave other results")

    # the repeated waves: a served wave that was seen before traces nothing
    t0 = trace_total()
    w0 = time.perf_counter()
    for name in ("what-if wave (params)", "mixed wave (batch)",
                 "transformer step (stepsim)"):
        ph[name]()
    log(f"repeated waves: {time.perf_counter() - w0:.3f} s, new traces "
        f"{trace_total() - t0}")
    if trace_total() != t0:
        failures.append(f"repeated waves traced {trace_total() - t0} "
                        "new programs")
    return chip


def smoke_one_chip(sizes: Sizes, cache: Optional[CacheEvents] = None,
                   log=print) -> List[str]:
    """Every one-chip phase; returns the failed checks (empty: passed)."""
    import jax
    from repro.obs import MetricsRegistry, global_metrics
    cache = cache or CacheEvents()
    failures: List[str] = []
    ph = phases(sizes)
    reg = MetricsRegistry()
    with global_metrics(reg):
        chip = _chip_phases(ph, cache, failures, log)
    for key, h in reg.snapshot()["histograms"].items():
        if ".compile_wall_s" in key:
            log(f"{key}: {h['sum']:.3f} s in {h['count']} dispatches that "
                "compiled (compile and first run)")

    cpu = jax.devices("cpu")[0]
    worst = 0.0
    with jax.default_device(cpu):
        for name, fn in ph.items():
            ref = fn()
            d = rel_diff(chip[name], ref)
            worst = max(worst, d)
            log(f"{name}: chip vs CPU max relative difference {d:.3e} "
                f"over {len(ref)} results")
    log(f"chip vs CPU: largest relative difference {worst:.3e} "
        f"(tolerance {CHIP_CPU_RTOL:g})")
    if not worst <= CHIP_CPU_RTOL:
        failures.append(f"chip vs CPU relative difference {worst:.3e} > "
                        f"{CHIP_CPU_RTOL:g}")

    (t_anchor,) = chip["verify anchor (single)"]
    log(f"verify anchor: {t_anchor!r} s (expected {ANCHOR_S})")
    if abs(t_anchor - ANCHOR_S) > 5e-8:
        failures.append(f"verify anchor {t_anchor!r} != {ANCHOR_S}")

    whatif = chip["what-if wave (params)"]
    # link_bw-major grid: more bandwidth or efficiency is never slower
    g = len(GEMM_SCALES)
    for i in range(len(whatif)):
        lx, gx = divmod(i, g)
        if (lx and whatif[i] > whatif[i - g]) or (gx and
                                                  whatif[i] > whatif[i - 1]):
            failures.append(f"what-if {i}: faster hardware predicted "
                            "slower")
    from repro.platforms import get_platform
    plat = get_platform("frontera")
    cfg = plat.hpl_config(**(sizes.frontera or {}))
    base = whatif[LINK_SCALES.index(1.0) * g + GEMM_SCALES.index(1.0)]
    tflops = cfg.flops() / base / 1e12
    pub = plat.scale.reported_tflops
    log(f"frontera: predicted {tflops!r} TFLOP/s on the device, published "
        f"{pub} ({(tflops - pub) / pub:+.2%}), N={cfg.N} nb={cfg.nb} "
        f"P={cfg.P} Q={cfg.Q}")
    if sizes.full and abs(tflops - pub) > PUBLISHED_RTOL * pub:
        failures.append(f"frontera {tflops:.1f} TFLOP/s is more than "
                        f"{PUBLISHED_RTOL:.0%} from the published {pub}")
    log(f"compile cache: {cache.hits} hits, {cache.misses} misses in all")
    return failures


def smoke_four_chips(sizes: Sizes, log=print) -> List[str]:
    """The what-if wave with its lanes sharded over every local device,
    against the same wave on one device."""
    import jax
    from repro.obs import global_metrics
    from repro.serve import PredictionService
    reqs = whatif_requests(sizes)
    base = serve(reqs)
    svc = PredictionService(shard=True)
    with global_metrics(svc.metrics):
        w0 = time.perf_counter()
        out = svc.predict_batch(whatif_requests(sizes))
        cold = time.perf_counter() - w0
        w0 = time.perf_counter()
        svc.predict_batch(whatif_requests(sizes))
        warm = time.perf_counter() - w0
    sharded = [out[r.rid]["time_s"] for r in reqs]
    snap = svc.metrics.snapshot()
    dispatches = snap["counters"].get("fastsim.sharded_dispatches", 0)
    shards = snap["gauges"].get("fastsim.shard_devices", {}).get("value")
    lanes = (snap["counters"].get("fastsim.lanes_live", 0)
             + snap["counters"].get("fastsim.lanes_padded", 0)) // 2
    log(f"sharded what-if wave: cold {cold:.3f} s, warm {warm:.3f} s; "
        f"{len(reqs)} requests padded to {int(lanes)} lanes; "
        f"fastsim.sharded_dispatches {int(dispatches)}; shards {shards}; "
        f"largest relative difference to unsharded "
        f"{rel_diff(sharded, base):.3e}")
    failures = []
    if sharded != base:
        failures.append("sharded results differ from unsharded")
    if not dispatches > 0:
        failures.append("no sharded dispatch ran")
    if shards != len(jax.devices()) or lanes % len(jax.devices()):
        failures.append(f"{shards} shards over {lanes} lanes on "
                        f"{len(jax.devices())} devices")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded what-if wave, over "
                         "four chips")
    args = ap.parse_args(argv)
    devs = require_tpu(args.chips)

    import jax
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    cache = CacheEvents()
    jax.monitoring.register_event_listener(cache)
    print(f"device: {devs[0].platform} {devs[0].device_kind} x {len(devs)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}")
    w0 = time.perf_counter()
    if args.chips == 4:
        failures = smoke_four_chips(FULL)
    else:
        failures = smoke_one_chip(FULL, cache)
    print(f"total wall {time.perf_counter() - w0:.1f} s")
    for f in failures:
        print(f"FAILED: {f}")
    if failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
