"""Batched sweep engine: batched-vs-loop agreement (including bucket
padding edge cases), compile-cache behavior, the sweep-aware whatif
grid, the batch-prediction service, and gradient calibration."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core.apps.hpl import HPLConfig
from repro.core import fastsim
from repro.core.fastsim import (FastSimParams, bucket_key,
                                simulate_hpl_fast, simulate_time_traced,
                                sweep_hpl)
from repro.core.hardware.node import local_node

BASE = FastSimParams.from_node(local_node(), link_bw=100e9 / 8)

# >= 20 mixed configs, covering P=1, Q=1, N % nb != 0, non-power-of-two
# grids, and repeated geometry (exercises the params-batched fast path).
CONFIGS = [
    HPLConfig(N=1024, nb=128, P=1, Q=1),
    HPLConfig(N=1000, nb=96, P=1, Q=4),      # N % nb != 0, P=1
    HPLConfig(N=2048, nb=128, P=4, Q=1),     # Q=1
    HPLConfig(N=3000, nb=128, P=2, Q=3),     # N % nb != 0
    HPLConfig(N=2048, nb=64, P=3, Q=5),
    HPLConfig(N=4096, nb=128, P=4, Q=4),
    HPLConfig(N=4096, nb=192, P=2, Q=8),
    HPLConfig(N=5000, nb=128, P=5, Q=7),     # N % nb != 0
    HPLConfig(N=3072, nb=96, P=7, Q=3),
    HPLConfig(N=8192, nb=256, P=6, Q=6),
    HPLConfig(N=1536, nb=128, P=1, Q=8),
    HPLConfig(N=1537, nb=128, P=8, Q=1),     # N % nb != 0, Q=1
    HPLConfig(N=2500, nb=100, P=2, Q=2),
    HPLConfig(N=6144, nb=192, P=4, Q=6),
    HPLConfig(N=2048, nb=128, P=2, Q=5),
    HPLConfig(N=4097, nb=128, P=3, Q=3),     # N % nb != 0
    HPLConfig(N=4096, nb=128, P=4, Q=4),     # duplicate geometry
    HPLConfig(N=4096, nb=128, P=4, Q=4),
    HPLConfig(N=7000, nb=224, P=5, Q=5),     # N % nb != 0
    HPLConfig(N=1024, nb=512, P=2, Q=2),     # 2 panels
    HPLConfig(N=512, nb=512, P=1, Q=1),      # single panel
]


def _params_for(i: int) -> FastSimParams:
    return dataclasses.replace(
        BASE, link_bw=BASE.link_bw * (1.0 + 0.15 * (i % 5)),
        gemm_eff=BASE.gemm_eff * (0.9 + 0.02 * (i % 4)),
        lookahead=float(i % 2))


def test_sweep_matches_loop_of_singles():
    prms = [_params_for(i) for i in range(len(CONFIGS))]
    batched = sweep_hpl(CONFIGS, prms)
    assert len(batched) == len(CONFIGS)
    for cfg, prm, b in zip(CONFIGS, prms, batched):
        single = simulate_hpl_fast(cfg, prm)
        rel = abs(b["time_s"] - single["time_s"]) / single["time_s"]
        assert rel < 1e-6, (cfg, rel)
        assert b["gflops"] == pytest.approx(single["gflops"], rel=1e-6)


def test_sweep_broadcasts_single_config_and_single_params():
    prms = [_params_for(i) for i in range(4)]
    res = sweep_hpl(CONFIGS[5], prms)
    assert len(res) == 4
    for prm, r in zip(prms, res):
        assert r["time_s"] == pytest.approx(
            simulate_hpl_fast(CONFIGS[5], prm)["time_s"], rel=1e-6)
    res = sweep_hpl(CONFIGS[:3], BASE)
    assert len(res) == 3
    with pytest.raises(ValueError):
        sweep_hpl(CONFIGS[:3], prms)


def test_params_only_change_does_not_retrace():
    cfg = HPLConfig(N=2048, nb=128, P=4, Q=4)
    simulate_hpl_fast(cfg, BASE)
    n0 = fastsim.trace_count()
    simulate_hpl_fast(cfg, dataclasses.replace(
        BASE, link_bw=1e9, gemm_eff=0.5, mem_bw=BASE.mem_bw * 3,
        lookahead=0.0, net_latency=5e-6))
    assert fastsim.trace_count() == n0


def test_sweep_cache_hits_after_warmup():
    prms = [_params_for(i) for i in range(len(CONFIGS))]
    sweep_hpl(CONFIGS, prms)
    n0 = fastsim.trace_count()
    sweep_hpl(CONFIGS, [_params_for(i + 7) for i in range(len(CONFIGS))])
    assert fastsim.trace_count() == n0


def test_nearby_geometries_share_buckets():
    # same panel/grid buckets -> same compiled program
    assert bucket_key(HPLConfig(N=2048, nb=128, P=5, Q=6)) == \
        bucket_key(HPLConfig(N=2048, nb=128, P=6, Q=5))
    # P=1 must get its own bucket (the column-sync branch is static)
    assert bucket_key(HPLConfig(N=2048, nb=128, P=1, Q=4))[1] == 1


def test_whatif_grid_rows_match_singles():
    from repro.core.predict import whatif_grid
    cfg = HPLConfig(N=4096, nb=128, P=4, Q=4)
    rows = whatif_grid(cfg, BASE, {"link_bw": [1.0, 2.0],
                                   "mem_bw": [1.0, 1.5]})
    assert len(rows) == 4
    for row in rows:
        prm = dataclasses.replace(BASE,
                                  link_bw=BASE.link_bw * row["link_bw"],
                                  mem_bw=BASE.mem_bw * row["mem_bw"])
        assert row["time_s"] == pytest.approx(
            simulate_hpl_fast(cfg, prm)["time_s"], rel=1e-6)
    base_t = simulate_hpl_fast(cfg, BASE)["time_s"]
    for row in rows:
        assert row["speedup"] == pytest.approx(base_t / row["time_s"],
                                               rel=1e-6)


def test_prediction_service_batches_and_matches():
    from repro.serve import HPLPredictionService, PredictRequest
    svc = HPLPredictionService(max_batch=8)
    reqs = [PredictRequest(rid=i, cfg=CONFIGS[i % 6],
                           params=_params_for(i)) for i in range(12)]
    out = svc.predict_batch(reqs)
    assert set(out) == set(range(12))
    assert svc.stats["requests"] == 12
    assert svc.stats["batches"] == 2          # 12 reqs / max_batch 8
    for req in reqs:
        assert out[req.rid]["time_s"] == pytest.approx(
            simulate_hpl_fast(req.cfg, req.params)["time_s"], rel=1e-6)


def test_gradient_flows_through_recurrence():
    cfg = HPLConfig(N=2048, nb=128, P=4, Q=4)
    with jax.enable_x64(True):
        g = jax.grad(lambda p: simulate_time_traced(cfg, p))(
            fastsim._f64_params(BASE))
    leaves = jax.tree_util.tree_leaves(g)
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
    # more bandwidth / efficiency => faster: negative sensitivities
    assert float(g.gemm_eff) < 0
    assert float(g.mem_bw) < 0
    assert float(g.link_bw) < 0
    assert float(g.net_latency) > 0


def test_calibration_recovers_true_params():
    from repro.core.calibrate import fit_fastsim_params
    true = BASE
    runs = []
    for (N, nb, P, Q) in [(2048, 128, 2, 4), (4096, 128, 4, 4),
                          (3072, 128, 4, 2), (4096, 192, 2, 8)]:
        cfg = HPLConfig(N=N, nb=nb, P=P, Q=Q)
        runs.append((cfg, simulate_hpl_fast(cfg, true)["time_s"]))
    init = dataclasses.replace(true, gemm_eff=true.gemm_eff * 1.6,
                               link_bw=true.link_bw * 0.5)
    fit = fit_fastsim_params(runs, init, fields=("gemm_eff", "link_bw"),
                             steps=250, lr=0.1)
    assert fit.loss < fit.loss0 / 100
    assert fit.params.gemm_eff == pytest.approx(true.gemm_eff, rel=0.05)
    assert fit.params.link_bw == pytest.approx(true.link_bw, rel=0.10)
