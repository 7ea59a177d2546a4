"""Batched sweep engine: batched-vs-loop agreement (including bucket
padding edge cases), compile-cache behavior, the sweep-aware whatif
grid, the batch-prediction service, and gradient calibration."""
import dataclasses

import jax
import jax.extend.core as jex
import numpy as np
import pytest

from repro.core.apps.hpl import HPLConfig, numroc
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
    from repro.platforms import get_platform
    from repro.serve import PredictionService, WorkloadRequest
    plat = get_platform("frontera")
    svc = PredictionService(max_batch=8)
    geoms = [{k: getattr(CONFIGS[i % 6], k) for k in ("N", "nb", "P", "Q")}
             for i in range(12)]
    reqs = [WorkloadRequest(rid=i, workload="hpl", platform="frontera",
                            params=g) for i, g in enumerate(geoms)]
    out = svc.predict_batch(reqs)
    assert set(out) == set(range(12))
    assert svc.stats["requests"] == 12
    assert svc.stats["batches"] == 2          # 12 reqs / max_batch 8
    for i, g in enumerate(geoms):
        ref = simulate_hpl_fast(plat.hpl_config(**g), plat.fastsim())
        assert out[i]["time_s"] == pytest.approx(ref["time_s"], rel=1e-6)


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
# step per panel, everything derived in the step) computed it on CPU.
# A one-row or one-column lane of a forced bucket runs with that side
# of the bucket cut to 1 (``_plan_forced``); its entry is its own
# single run's answer (``simulate_hpl_fast``), bitwise
GOLDEN = {
    "batch_mixed": [
        "0x1.81ccea9b7cfa1p-1", "0x1.f9cae103d52a0p-3",
        "0x1.a352c2c859212p-3", "0x1.7d6f6dee4c691p-4",
        "0x1.6f16a28fc15f8p-2", "0x1.71a4f43e1e9a6p-4",
    ],
    "batch_p1": [
        "0x1.1b192271a6876p-1", "0x1.639eeb6ba8dfdp-4",
        "0x1.19dafaee5a446p-7",
    ],
    "batch_q1": [
        "0x1.abdfe9720d91bp-6", "0x1.25c89c913f492p-6",
        "0x1.ec458e4001e86p-8",
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


@pytest.mark.parametrize("P, Q", [(1, 4), (4, 1), (1, 1), (2, 4)])
def test_forced_bucket_answers_as_the_grids_own(P, Q):
    """A forced bucket changes no answer: a one-row or one-column grid
    among two-dimensional ones reads bitwise as its own single run, as a
    two-dimensional grid does, and the wave costs one call per kind."""
    cfgs = [HPLConfig(N=4000, nb=128, P=P, Q=Q),
            HPLConfig(N=3000, nb=96, P=3, Q=3)]
    prms = [_params_for(0), _params_for(1)]
    forced = sweep_hpl(cfgs, prms, bucket=(32, 4, 4))
    for cfg, prm, r in zip(cfgs, prms, forced):
        assert r["time_s"].hex() == simulate_hpl_fast(cfg, prm)[
            "time_s"].hex(), cfg
    calls = fastsim._plan_forced(cfgs, prms, [(), ()], (32, 4, 4))
    assert [c[3] for c in calls] == (
        [(32, 4, 4)] if P > 1 and Q > 1 else
        [(32, 4 if P > 1 else 1, 4 if Q > 1 else 1), (32, 4, 4)])


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
    recorded when the state took row classes: node-aware pricing adds
    nothing to them (tests/data/fastsim_node_blind_jaxpr.txt)."""
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


# ------------------------------------ row classes: the state's slots

def _numroc_np(n, nb, iproc, nprocs):
    """ScaLAPACK's NUMROC (``apps.hpl.numroc``) over NumPy arrays."""
    nblocks = n // nb
    extra = nblocks % nprocs
    return (nblocks // nprocs) * nb + np.where(
        iproc < extra, nb, np.where(iproc == extra, n % nb, 0))


def _check_row_classes(N, nb, P, n_panels_max=None, P_max=None):
    """The table pass's row classes against every process row's local
    rows, in every panel of the bucket (or of ``n_panels_max``) from the
    one before panel 0 to the padding panels past the live count, on
    ``P_max`` process rows (the bucket's by default)."""
    P_max = P_max or fastsim._bucket(P)
    n_panels_max = n_panels_max or fastsim._bucket(-(-N // nb))
    ks = np.arange(-1, n_panels_max + 1)
    with jax.enable_x64(True):
        _, _, _, rows, case = fastsim._geometry(
            N // nb, N % nb, nb, P, P_max, jax.numpy.asarray(ks))
        n_rows = np.asarray(fastsim._slot_rows(case, P))
    rows, case = np.asarray(rows), np.asarray(case)
    # panel k deals its N - k*nb trailing rows from process row k % P
    p = np.arange(P)
    mloc = _numroc_np(np.maximum(N - ks * nb, 0)[:, None], nb,
                      (p[None, :] - ks[:, None]) % P, P)       # (K, P)
    if len(ks) <= 80:
        assert mloc.tolist() == [
            [numroc(max(N - k * nb, 0), nb, (q - k) % P, P) for q in p]
            for k in ks]
    # each process row's case holds its local rows ...
    np.testing.assert_array_equal(
        np.take_along_axis(rows, case[:, :P], axis=1), mloc)
    # ... and each slot counts the live rows of its pair of cases
    assert n_rows.shape == (len(ks) - 1, fastsim._SLOTS)
    assert (n_rows.sum(axis=1) == P).all()
    for i in range(len(ks) - 1):
        slots = {divmod(s, fastsim._CASES): n
                 for s, n in enumerate(n_rows[i]) if n}
        pairs = list(zip(case[i, :P].tolist(), case[i + 1, :P].tolist()))
        assert slots == {pr: pairs.count(pr) for pr in set(pairs)}
        assert ({(rows[i, a], rows[i + 1, b]) for a, b in slots}
                == set(zip(mloc[i].tolist(), mloc[i + 1].tolist())))
    return n_rows


@pytest.mark.parametrize("N,nb,P", [
    (9282848, 384, 88),        # Frontera's published run
    (16473600, 384, 144),      # Summit's: N % nb == 0
    (32785, 32, 5),            # params_blocks: a trailing 17-column panel
    (4096, 128, 1),            # P = 1: one case, the tail, every panel
    (100, 128, 3),             # one partial panel, fewer panels than rows
    (12000, 32, 11),           # N % nb == 0, a 12-row bucket
])
def test_row_classes_match_numroc(N, nb, P):
    n_rows = _check_row_classes(N, nb, P)
    # at most four slots a panel are filled, of seven that ever are
    assert (n_rows > 0).sum(axis=1).max() <= 4
    assert n_rows[:, [1, 3]].sum() == 0


def test_row_classes_match_numroc_random_geometries():
    # one shape for all (64 panels, 48 process rows), so that the table
    # pass compiles once: up to 48 live panels and 40 live rows
    rng = np.random.default_rng(17)
    for _ in range(200):
        nb = int(rng.integers(1, 257))
        _check_row_classes(int(rng.integers(1, 48 * nb + 1)), nb,
                           int(rng.integers(1, 41)), 64, 48)


# float.hex of answers in buckets of more than 9 process rows, as the
# program with a state row per process row (before the state held row
# classes) computed them on CPU
GOLDEN_ROWS_CASES = {
    # params mode, P = 11 in a 12-row bucket, N % nb == 0, two blocks
    "params_p11": lambda: _golden_sweep(
        [HPLConfig(N=12000, nb=32, P=11, Q=5)] * 3),
    # batch mode in a 16-row bucket, a P = 1 lane among them
    "batch_p16": lambda: _golden_sweep(
        [HPLConfig(N=9001, nb=32, P=13, Q=3),
         HPLConfig(N=7000, nb=48, P=11, Q=4),
         HPLConfig(N=6400, nb=64, P=10, Q=2),
         HPLConfig(N=5000, nb=32, P=16, Q=4),
         HPLConfig(N=3000, nb=64, P=1, Q=4)], bucket=(384, 16, 4)),
    "single_p13": lambda: [simulate_hpl_fast(
        HPLConfig(N=9001, nb=32, P=13, Q=3), _params_for(2))["time_s"]],
    # node-aware params: 3 ranks a node down the columns of P = 12
    "nodes_params_p12": lambda: [r["time_s"] for r in sweep_hpl(
        HPLConfig(N=10000, nb=32, P=12, Q=4),
        [_on_nodes(_params_for(i), 3) for i in range(3)])],
    # node-aware params, nodes of whole rows under the row mapping
    "nodes_params_row": lambda: [r["time_s"] for r in sweep_hpl(
        HPLConfig(N=8000, nb=32, P=11, Q=2, pmap="row"),
        [_on_nodes(_params_for(i), 4) for i in range(2)])],
    # node-aware batch with a node-blind lane, a 16-row bucket
    "nodes_batch_p16": lambda: [r["time_s"] for r in sweep_hpl(
        [HPLConfig(N=9000, nb=32, P=14, Q=3),
         HPLConfig(N=7001, nb=48, P=12, Q=4, pmap="row"),
         HPLConfig(N=6000, nb=64, P=11, Q=2)],
        [_on_nodes(_params_for(0), 2), _on_nodes(_params_for(1), 2),
         _params_for(2)], bucket=(384, 16, 4))],
}

GOLDEN_ROWS = {
    "params_p11": ["0x1.085fdac8ae8a3p-2", "0x1.e176a98efdd34p-3",
                   "0x1.0428d1a22b38ap-2"],
    "batch_p16": ["0x1.806c81854b062p-3", "0x1.0f266bfc0afcep-3",
                  "0x1.148d8230736a3p-3", "0x1.6e8942f08f591p-4",
                  "0x1.4a9bc5d171ee0p-5"],    # the 1 x 4 lane, as GOLDEN
    "single_p13": ["0x1.7a1c3ee5fa550p-3"],
    "nodes_params_p12": ["0x1.b6ab72b826017p-3", "0x1.94fc69d3e5a81p-3",
                         "0x1.ad6471aa5f864p-3"],
    "nodes_params_row": ["0x1.779db70e5b2f1p-3", "0x1.5125e9c779145p-3"],
    "nodes_batch_p16": ["0x1.819688fbd7346p-3", "0x1.13396d965bbc5p-3",
                        "0x1.f87a64d51c5fbp-4"],
}


@pytest.mark.parametrize("case", sorted(GOLDEN_ROWS_CASES))
def test_row_class_state_matches_per_row_answers_bitwise(case):
    assert [t.hex() for t in GOLDEN_ROWS_CASES[case]()] == GOLDEN_ROWS[case]


# gradients of the per-row program (as GOLDEN_ROWS): a max shares a tie
# among the tied process rows, so a slot weighs as the rows it holds
GOLDEN_ROWS_GRAD = {
    "params_p11": (HPLConfig(N=12000, nb=32, P=11, Q=5), 1, {
        "peak_flops": "-0x1.60f276a51bf3fp-52",
        "gemm_eff": "-0x1.cc91feca2360cp-12",
        "mem_bw": "-0x1.59bc3e201102ep-43",
        "theta": "0x1.1c30000000000p+15",
        "link_bw": "-0x1.28b4c4208efc4p-41",
        "net_latency": "0x1.7fc9000000000p+16",
        "hop_latency": "0x0.0p+0",
        "bcast_bw_scale": "-0x1.9c60482d7d693p-9",
        "swap_bw_scale": "-0x1.a3debd7b5ceabp-8",
        "lookahead": "-0x1.45a3105528bfdp-6"}),
    "nodes_p12": (HPLConfig(N=10000, nb=32, P=12, Q=4), 3, {
        "peak_flops": "-0x1.c62880bb38bf7p-53",
        "gemm_eff": "-0x1.28525adf54f00p-12",
        "mem_bw": "-0x1.d94158794a0d1p-44",
        "theta": "0x1.d9a8000000001p+14",
        "link_bw": "-0x1.6b9705c6d501ap-41",
        "net_latency": "0x1.3fd3800000000p+16",
        "hop_latency": "0x0.0p+0",
        "bcast_bw_scale": "-0x1.934c24502953cp-8",
        "swap_bw_scale": "-0x1.6be32cc03f760p-8",
        "lookahead": "-0x1.b525c04316483p-7"}),
}


@pytest.mark.parametrize("case", sorted(GOLDEN_ROWS_GRAD))
def test_row_class_gradient_matches_per_row_gradient(case):
    cfg, R, want = GOLDEN_ROWS_GRAD[case]
    prm = _params_for(3)
    if R > 1:
        prm = _on_nodes(prm, R)
    with jax.enable_x64(True):
        g = jax.grad(lambda p: simulate_time_traced(cfg, p))(
            fastsim._f64_params(prm))
    for name, w in want.items():
        w = float.fromhex(w)
        assert abs(float(getattr(g, name)) - w) <= 1e-12 * abs(w), name


def test_rows_carried_and_bucket_rows_are_counted():
    """A params call of 4 lanes and a batch call of 2 in a 12-row bucket
    count 9 state rows a lane carried and 12 bucket rows a lane; with the
    registry off nothing is counted, and the answers are the same."""
    from repro.obs import MetricsRegistry, global_metrics

    def calls():
        cfg = HPLConfig(N=3000, nb=64, P=11, Q=3)
        return (sweep_hpl(cfg, [_params_for(i) for i in range(3)])
                + sweep_hpl([cfg, HPLConfig(N=2000, nb=64, P=5, Q=3)],
                            [BASE, BASE], bucket=(64, 12, 4)))
    reg = MetricsRegistry()
    with global_metrics(reg):
        on = calls()
    c = reg.snapshot()["counters"]
    assert c["fastsim.rows_carried"] == (4 + 2) * fastsim._SLOTS
    assert c["fastsim.rows_bucket"] == (4 + 2) * 12
    assert c["fastsim.lanes_live"] + c["fastsim.lanes_padded"] == 4 + 2
    with global_metrics(None):
        off = calls()
        assert fastsim.get_global_metrics().snapshot()["counters"] == {}
    assert off == on
    assert reg.snapshot()["counters"] == c
