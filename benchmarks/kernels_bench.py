"""Per-Pallas-kernel microbench: error against the jnp references, warm
wall time per call, and analytic TPU-roofline times for the shapes run.

The kernels compile for the TPU.  Off the chip ``interpret=True``
(``--interpret``) runs their bodies in Python instead, for correctness
checks; it is refused on a TPU, so a chip run never times the
interpreter.

    PYTHONPATH=src python benchmarks/kernels_bench.py [--full] [--interpret]
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def _maxerr(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _warm_call(fn, *args):
    """Compile with one call, then time one more ending in
    ``block_until_ready``; returns (output, seconds)."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def run(quick: bool = True, interpret: bool = False):
    dev = jax.devices()[0]
    if interpret == (dev.platform == "tpu"):
        raise ValueError(f"kernels_bench: on {dev.platform!r} pass "
                         f"interpret={dev.platform != 'tpu'}: the kernels "
                         "compile only for a TPU, and the interpreter is "
                         "for checks off the chip")
    where = f"device={dev.platform}:{dev.device_kind};interpret={interpret}"
    rows = []
    key = jax.random.PRNGKey(0)

    # flash attention
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    b, s, g, r, hd = (1, 256, 1, 4, 64) if quick else (2, 1024, 2, 4, 128)
    q = jax.random.normal(key, (b, s, g, r, hd))
    k = jax.random.normal(key, (b, s, g, hd))
    v = jax.random.normal(key, (b, s, g, hd))
    out, wall = _warm_call(
        lambda *a: flash_attention(*a, causal=True, interpret=interpret),
        q, k, v)
    err = _maxerr(out, attention_ref(q, k, v))
    # analytic TPU time at roofline: 2*2*B*S^2*G*R*hd flops (causal /2)
    flops = 2 * 2 * b * s * s * g * r * hd / 2
    rows.append({"name": "kern.flash_attention",
                 "us_per_call": wall * 1e6,
                 "derived": f"err={err:.2e};tpu_roofline_us="
                            f"{flops/197e12*1e6:.2f};{where}"})

    # ssd
    from repro.kernels.ssd_scan.ops import ssd
    from repro.kernels.ssd_scan.ref import ssd_ref_sequential
    bs, ss, hh, pp, nn = (1, 128, 2, 16, 8) if quick else (2, 512, 4, 64, 128)
    xh = jax.random.normal(key, (bs, ss, hh, pp))
    dt = jax.nn.softplus(jax.random.normal(key, (bs, ss, hh)))
    A = -jnp.exp(jax.random.normal(key, (hh,)))
    Bh = jax.random.normal(key, (bs, ss, hh, nn))
    Ch = jax.random.normal(key, (bs, ss, hh, nn))
    # the (1, chunk) dt row block must be lane-aligned or the whole sequence
    y, wall = _warm_call(
        lambda *a: ssd(*a, chunk=ss if quick else 128, interpret=interpret),
        xh, dt, A, Bh, Ch)
    err = _maxerr(y, ssd_ref_sequential(xh, dt, A, Bh, Ch))
    rows.append({"name": "kern.ssd_scan", "us_per_call": wall * 1e6,
                 "derived": f"err={err:.2e};{where}"})

    # maxmin
    from repro.kernels.maxmin_fair.ops import waterfill
    from repro.kernels.maxmin_fair.ref import waterfill_ref
    F, L = (128, 128) if quick else (1024, 1024)
    adj = (jax.random.uniform(key, (F, L)) < 0.05).astype(jnp.int8)
    caps = jax.random.uniform(key, (L,)) * 1e9 + 1e8
    rk, wall = _warm_call(
        lambda *a: waterfill(*a, use_kernel=True, interpret=interpret),
        adj, caps)
    err = _maxerr(jnp.minimum(rk, 1e30),
                  jnp.minimum(waterfill_ref(adj, caps), 1e30))
    rows.append({"name": "kern.maxmin_waterfill",
                 "us_per_call": wall * 1e6,
                 "derived": f"err={err:.2e};F={F};L={L};{where}"})
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="larger shapes")
    ap.add_argument("--interpret", action="store_true",
                    help="run kernel bodies in Python (off the chip only)")
    args = ap.parse_args()
    for r in run(quick=not args.full, interpret=args.interpret):
        print(r)
