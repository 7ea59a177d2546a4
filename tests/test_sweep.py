"""Batched sweep engine: batched-vs-loop agreement (including bucket
padding edge cases), compile-cache behavior, the sweep-aware whatif
grid, the batch-prediction service, and gradient calibration."""
import dataclasses

import jax
import jax.extend.core as jex
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


def _golden_sweep(configs, bucket=None):
    prms = [_params_for(i) for i in range(len(configs))]
    return [r["time_s"] for r in sweep_hpl(configs, prms, bucket=bucket)]


# Bucket shapes against the recurrence's blocks of panels (DESIGN.md §10):
# 1536 panels are six blocks, 384 three, 512 two; 32 and 64 one each.
GOLDEN_CASES = {
    # params mode, 1025 panels: the trailing 17-column panel opens block
    # 4 and the padding panels 1025..1535 cross the block boundary at 1280
    "params_blocks": lambda: _golden_sweep(
        [HPLConfig(N=32785, nb=32, P=5, Q=7)] * 3),
    # params mode in a single block
    "params_one_block": lambda: _golden_sweep(
        [HPLConfig(N=4096, nb=128, P=4, Q=4)] * 2),
    # batch mode, mixed geometries (P = 1 and Q = 1 among them) forced
    # into one bucket of two blocks
    "batch_mixed": lambda: _golden_sweep(
        [HPLConfig(N=16000, nb=32, P=2, Q=3),
         HPLConfig(N=9000, nb=64, P=1, Q=4),
         HPLConfig(N=8200, nb=32, P=4, Q=1),
         HPLConfig(N=4096, nb=128, P=1, Q=1),
         HPLConfig(N=12345, nb=48, P=3, Q=3),
         HPLConfig(N=6000, nb=96, P=4, Q=4)], bucket=(512, 4, 4)),
    # batch mode with P_max == 1 (no column sync), three blocks of 128
    "batch_p1": lambda: _golden_sweep(
        [HPLConfig(N=12000, nb=32, P=1, Q=4),
         HPLConfig(N=5000, nb=64, P=1, Q=3),
         HPLConfig(N=1000, nb=96, P=1, Q=1)], bucket=(384, 1, 4)),
    # batch mode with Q_max == 1 (no ring to re-base)
    "batch_q1": lambda: _golden_sweep(
        [HPLConfig(N=2048, nb=128, P=4, Q=1),
         HPLConfig(N=1537, nb=128, P=3, Q=1),
         HPLConfig(N=1000, nb=64, P=1, Q=1)], bucket=(64, 4, 1)),
    # single mode, one block and three blocks
    "single": lambda: [
        simulate_hpl_fast(HPLConfig(N=5000, nb=128, P=5, Q=7),
                          BASE)["time_s"],
        simulate_hpl_fast(HPLConfig(N=40000, nb=128, P=2, Q=3),
                          _params_for(1))["time_s"]],
}

# float.hex of each answer as the per-panel recurrence (one fori_loop
# step per panel, everything derived in the step) computed it on CPU
GOLDEN = {
    "batch_mixed": [
        "0x1.81ccea9b7cfa1p-1", "0x1.ff44d3383a8c6p-3",
        "0x1.ad33a12f409ffp-3", "0x1.96e2bb4ba3616p-4",
        "0x1.6f16a28fc15f8p-2", "0x1.71a4f43e1e9a6p-4",
    ],
    "batch_p1": [
        "0x1.1b192271a6876p-1", "0x1.639eeb6ba8dfdp-4",
        "0x1.24cb3ca76ec65p-7",
    ],
    "batch_q1": [
        "0x1.abdfe9720d91bp-6", "0x1.25c89c913f492p-6",
        "0x1.f4446042989bap-8",
    ],
    "params_blocks": [
        "0x1.79904ce4835aap+0", "0x1.f7564df11ea45p-1",
        "0x1.6b4392942a118p+0",
    ],
    "params_one_block": [
        "0x1.f78f21c218b45p-5", "0x1.dc5ae32740c3fp-5",
    ],
    "single": [
        "0x1.721e926fad5a2p-4", "0x1.d95ef0ed59634p+2",
    ],
}


@pytest.mark.parametrize("case", sorted(GOLDEN_CASES))
def test_block_recurrence_matches_per_panel_answers_bitwise(case):
    assert [t.hex() for t in GOLDEN_CASES[case]()] == GOLDEN[case]


GOLDEN_GRAD_CFG = HPLConfig(N=40000, nb=128, P=2, Q=3)   # three blocks
GOLDEN_GRAD = {
    "peak_flops": "-0x1.52a5424b34108p-38",
    "gemm_eff": "-0x1.b9e863d7a8dfcp+2",
    "mem_bw": "-0x1.4ecc8497b2251p-38",
    "theta": "0x1.6a76000000000p+15",
    "link_bw": "-0x1.20d0331ff893cp-36",
    "net_latency": "0x1.ecaa000000000p+14",
    "hop_latency": "0x0.0p+0",
    "bcast_bw_scale": "-0x1.7087c8e825c3bp-3",
    "swap_bw_scale": "-0x1.e1bf4a5a6efafp-4",
    "lookahead": "-0x1.67e9d6f0f4580p-2",
}


def _golden_grad():
    with jax.enable_x64(True):
        g = jax.grad(lambda p: simulate_time_traced(GOLDEN_GRAD_CFG, p))(
            fastsim._f64_params(_params_for(3)))
    return {n: float(getattr(g, n)) for n in fastsim._PARAM_FIELDS}


def test_block_recurrence_gradient_matches_per_panel_gradient():
    g = _golden_grad()
    for name, want in GOLDEN_GRAD.items():
        want = float.fromhex(want)
        assert abs(g[name] - want) <= 1e-12 * abs(want), name


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(sub, jex.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jex.Jaxpr):
                    yield from _eqns(sub)


def _int_divisions(jaxpr):
    return [e.primitive.name for e in _eqns(jaxpr)
            if e.primitive.name in ("div", "rem")
            and np.issubdtype(e.outvars[0].aval.dtype, np.integer)]


@pytest.mark.parametrize("mode", ["single", "params", "batch"])
def test_serial_panel_step_does_no_integer_division(mode):
    # the panel-invariant geometry (NUMROC, widths, owners) belongs to
    # the once-a-block tables, never to the serial step
    lanes = () if mode == "single" else (4,)
    geom = np.full(lanes if mode == "batch" else (), 4, np.int64)
    prm = FastSimParams(**{n: np.ones(lanes) for n in fastsim._PARAM_FIELDS})
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(fastsim._compiled(1536, 6, 8, mode))(
            geom, geom, geom, geom, prm).jaxpr
    outer, inner = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"]
    assert outer.params["length"] == 6 and inner.params["length"] == 256
    assert _int_divisions(outer.params["jaxpr"].jaxpr)
    assert not _int_divisions(inner.params["jaxpr"].jaxpr)


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


# --------------------------------------------- one rank per node: pinned

# node-blind answers as the recurrence gave them before it learned about
# nodes (float.hex), in each mode
GOLDEN_R1 = {
    "single": ["0x1.59308c3c9e1d0p-5"],
    "params": ["0x1.f2fa12f18d0b1p-5", "0x1.db1547e080effp-5",
               "0x1.dd46a5966975ap-5"],
    "batch": ["0x1.f2fa12f18d0b1p-5", "0x1.81fba8a6c1e0ep-5",
              "0x1.f61fc801fd9c4p-7"],
}


def _golden_params():
    return [dataclasses.replace(BASE, link_bw=BASE.link_bw * s,
                                lookahead=float(i % 2))
            for i, s in enumerate((0.7, 1.0, 1.9))]


@pytest.mark.parametrize("mode", ["single", "params", "batch"])
@pytest.mark.parametrize("pmap", ["col", "row"])
def test_one_rank_per_node_answers_are_pinned(mode, pmap):
    """R = 1 under either mapping is the node-blind recurrence, bitwise."""
    prms = _golden_params()
    if mode == "single":
        got = [simulate_hpl_fast(HPLConfig(N=3000, nb=128, P=3, Q=4,
                                           pmap=pmap), BASE)["time_s"]]
    elif mode == "params":
        got = [r["time_s"] for r in sweep_hpl(
            HPLConfig(N=4096, nb=128, P=4, Q=6, pmap=pmap), prms)]
    else:
        cfgs = [HPLConfig(N=4096, nb=128, P=4, Q=6, pmap=pmap),
                HPLConfig(N=3500, nb=96, P=3, Q=5, pmap=pmap),
                HPLConfig(N=2048, nb=64, P=2, Q=8, pmap=pmap)]
        got = [r["time_s"] for r in sweep_hpl(cfgs, prms, bucket=(64, 4, 8))]
    assert [t.hex() for t in got] == GOLDEN_R1[mode]


def _jaxpr_text(mode):
    """The node-blind program of ``mode`` at bucket 1536 x 6 x 8, printed
    without source information, one run of spaces before each name-stack
    comment."""
    import re
    lanes = () if mode == "single" else (4,)
    geom = np.full(lanes if mode == "batch" else (), 4, np.int64)
    prm = FastSimParams(**{n: np.ones(lanes) for n in fastsim._PARAM_FIELDS})
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(fastsim._compiled(1536, 6, 8, mode))(
            geom, geom, geom, geom, prm)
    text = closed.jaxpr.pretty_print(source_info=False, name_stack=True)
    return "".join(re.sub(r" {2,}#", " #", line).rstrip() + "\n"
                   for line in (f"# mode {mode}, bucket 1536x6x8\n"
                                + text + "\n").splitlines())


def test_node_blind_program_is_unchanged():
    """The one-rank-per-node programs are equation for equation those
    recorded before the node-aware recurrence existed
    (tests/data/fastsim_node_blind_jaxpr.txt)."""
    from pathlib import Path
    want = (Path(__file__).parent / "data"
            / "fastsim_node_blind_jaxpr.txt").read_text()
    got = "".join(_jaxpr_text(m) for m in ("single", "params", "batch"))
    assert got == want


def _on_nodes(prm, R, intra_latency=1.5e-6):
    """``prm`` with ``R`` ranks a node and an intra-node latency."""
    return dataclasses.replace(prm, ranks_per_node=R,
                               intra_latency=intra_latency)


def test_node_blind_lane_in_node_aware_program_is_bitwise():
    """A batch that mixes machines of one and of several ranks per node
    runs one node-aware program; its one-rank lanes answer bitwise what
    the node-blind program answers."""
    cfgs = [HPLConfig(N=3000, nb=128, P=4, Q=4),
            HPLConfig(N=2900, nb=128, P=4, Q=6, pmap="row"),
            HPLConfig(N=2800, nb=96, P=3, Q=5)]
    prms = [_on_nodes(BASE, 2), _on_nodes(BASE, 3), BASE]
    mixed = sweep_hpl(cfgs, prms, bucket=(32, 4, 6))
    alone = sweep_hpl(cfgs[2:], prms[2:], bucket=(32, 4, 6))
    assert mixed[2]["time_s"] == alone[0]["time_s"]
    blind = sweep_hpl(cfgs[:2], [BASE, BASE], bucket=(32, 4, 6))
    assert all(m["time_s"] != b["time_s"] for m, b in zip(mixed, blind))


@pytest.mark.parametrize("P,Q,R,pmap,block", [
    (8, 4, 4, "col", (4, 1)), (4, 8, 4, "col", (4, 1)),
    (2, 8, 4, "col", (2, 2)), (4, 8, 4, "row", (1, 4)),
    (8, 4, 4, "row", (1, 4)), (8, 2, 4, "row", (2, 2)),
    (5, 5, 1, "row", None)])
def test_node_block(P, Q, R, pmap, block):
    cfg = HPLConfig(N=1024, nb=128, P=P, Q=Q, pmap=pmap)
    assert fastsim.node_block(cfg, R) == block


def test_node_aware_modes_agree_and_do_not_retrace():
    """single, params and batch give one answer for a node-aware run;
    another R or mapping on the same grid reuses the compiled program,
    and never shares a params call with a different node block."""
    cfg = HPLConfig(N=2900, nb=128, P=4, Q=6, pmap="row")
    prms = [_on_nodes(p, 3) for p in _golden_params()]
    params = [r["time_s"] for r in sweep_hpl(cfg, prms)]
    single = [simulate_hpl_fast(cfg, p)["time_s"] for p in prms]
    batch = [r["time_s"] for r in sweep_hpl([cfg] * 3, prms,
                                            bucket=(32, 4, 6))]
    np.testing.assert_allclose(params, single, rtol=1e-13)
    np.testing.assert_allclose(batch, single, rtol=1e-13)
    n0 = fastsim.trace_count()
    sweep_hpl(dataclasses.replace(cfg, pmap="col"),
              [_on_nodes(p, 2, 2e-6) for p in prms])
    assert fastsim.trace_count() == n0
    plan = fastsim._plan([cfg] * 4, [prms[0]] * 2 + [BASE] * 2,
                         [(1, 3, 1.5e-6)] * 2 + [()] * 2)
    assert [(p[2], p[6]) for p in plan] == [([0, 1], True), ([2, 3], False)]


@pytest.mark.parametrize("mode", ["single", "params", "batch"])
def test_node_aware_serial_step_does_no_integer_division(mode):
    lanes = () if mode == "single" else (4,)
    geom = np.full(lanes if mode == "batch" else (), 4, np.int64)
    prm = FastSimParams(**{n: np.ones(lanes) for n in fastsim._PARAM_FIELDS})
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(fastsim._compiled(1536, 6, 8, mode, True))(
            geom, geom, geom, geom, prm, geom, geom,
            np.ones(lanes)).jaxpr
    outer, inner = [e for e in _eqns(jaxpr) if e.primitive.name == "scan"]
    assert _int_divisions(outer.params["jaxpr"].jaxpr)
    assert not _int_divisions(inner.params["jaxpr"].jaxpr)


def test_node_aware_lanes_are_counted():
    from repro.obs import MetricsRegistry, global_metrics
    cfg = HPLConfig(N=2900, nb=128, P=4, Q=6, pmap="row")
    reg = MetricsRegistry()
    with global_metrics(reg):
        sweep_hpl([cfg] * 3, [_on_nodes(BASE, 3)] * 3)
        sweep_hpl([cfg] * 2, [BASE] * 2)
    c = reg.snapshot()["counters"]
    assert c["fastsim.lanes_node_aware"] == 3
    assert c["fastsim.lanes_live"] == 5


def test_intra_node_latency_is_a_lane_parameter():
    """Each lane prices its hops inside a node at its own latency, in one
    params call: a lane's answer does not depend on its neighbours', and
    a longer intra-node latency makes a run no faster."""
    cfg = HPLConfig(N=2900, nb=128, P=4, Q=6, pmap="row")
    lats = [1e-7, 1.5e-6, 5e-5]
    prms = [_on_nodes(BASE, 3, a) for a in lats]
    plan = fastsim._plan([cfg] * 3, prms,
                         [fastsim._node_args(cfg, p) for p in prms])
    assert len(plan) == 1
    together = [r["time_s"] for r in sweep_hpl(cfg, prms)]
    alone = [simulate_hpl_fast(cfg, p)["time_s"] for p in prms]
    np.testing.assert_allclose(together, alone, rtol=1e-13)
    assert together[0] < together[1] < together[2]


def test_several_ranks_a_node_need_the_intra_node_latency():
    cfg = HPLConfig(N=2900, nb=128, P=4, Q=6, pmap="row")
    with pytest.raises(ValueError, match="intra_latency"):
        simulate_hpl_fast(cfg, dataclasses.replace(BASE, ranks_per_node=3))
    with pytest.raises(ValueError, match="intra_latency"):
        sweep_hpl([cfg] * 2, [dataclasses.replace(BASE, ranks_per_node=3)]
                  * 2)
