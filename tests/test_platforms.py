"""Platform layer: registry integrity, both-backend builds, DES vs
fastsim cross-validation on every registry machine, and the DES->fastsim
calibration bridge (Table II acceptance band)."""
import dataclasses

import pytest

from repro.core.apps.hpl import HPLConfig, HPLSim
from repro.core.fastsim import FastSimParams, simulate_hpl_fast
from repro.core.hardware.node import NodeModel
from repro.core.hardware.topology import Topology
from repro.platforms import (Platform, get_platform, list_platforms)

ALL_NAMES = list_platforms()

# Expected registry backbone (the paper's machines + fabric diversity).
PAPER_NAMES = {"bdw-local", "frontera", "pupmaya", "paper-fat-tree-10008",
               "tpu-v5e-pod"}


def _small_cfg(plat: Platform) -> HPLConfig:
    """N~2k probe sized to the platform: 8 ranks spread over >= 2 nodes."""
    rpn = plat.scale.ranks_per_node
    P, Q = 2, 4
    assert P * Q <= plat.scale.n_ranks
    assert P * Q > rpn or rpn == 1      # spans nodes, not one self-send box
    return HPLConfig(N=2048, nb=128, P=P, Q=Q, lookahead=0,
                     bcast=plat.mpi.bcast)


# ---------------------------------------------------------------- registry

def test_registry_contains_paper_machines_and_fabric_diversity():
    assert PAPER_NAMES <= set(ALL_NAMES)
    assert len(ALL_NAMES) >= 13
    kinds = {get_platform(n).fabric.kind for n in ALL_NAMES}
    assert {"fat-tree", "dragonfly", "torus", "multipod"} <= kinds


def test_specs_are_frozen():
    plat = get_platform("frontera")
    with pytest.raises(dataclasses.FrozenInstanceError):
        plat.name = "x"
    with pytest.raises(dataclasses.FrozenInstanceError):
        plat.node.peak_flops = 1.0
    with pytest.raises(dataclasses.FrozenInstanceError):
        plat.scale.n_nodes = 2


@pytest.mark.parametrize("name", ALL_NAMES)
def test_spec_serialization_round_trip(name):
    plat = get_platform(name)
    assert Platform.from_dict(plat.to_dict()) == plat
    assert Platform.from_json(plat.to_json()) == plat


@pytest.mark.parametrize("name", ALL_NAMES)
def test_platform_builds_both_backends(name):
    plat = get_platform(name)
    stack = plat.des()
    assert isinstance(stack.node, NodeModel)
    assert isinstance(stack.topology, Topology)
    assert stack.topology.n_links > 0
    assert stack.ranks_per_node >= 1
    # grid fits the machine
    P, Q = plat.scale.grid
    assert 0 < P * Q <= plat.scale.n_ranks
    prm = plat.fastsim()
    assert isinstance(prm, FastSimParams)
    for field in ("peak_flops", "mem_bw", "link_bw", "gemm_eff"):
        assert getattr(prm, field) > 0, field
    cfg = plat.hpl_config()
    assert cfg.n_ranks == P * Q


def test_registry_unknown_name_suggests_close_matches():
    # a near-miss gets a difflib suggestion, not a registry dump
    with pytest.raises(KeyError, match="frontera"):
        get_platform("fronterra")
    with pytest.raises(KeyError, match="platforms registered"):
        get_platform("no-such-machine")


def test_hplsim_accepts_platform_and_matches_explicit_build():
    plat = get_platform("bdw-local")
    cfg = _small_cfg(plat)
    via_platform = HPLSim(cfg, plat).run()
    stack = plat.des()
    explicit = HPLSim(cfg, stack.node, stack.topology,
                      ranks_per_node=stack.ranks_per_node,
                      mpi_overhead=stack.mpi_overhead).run()
    assert via_platform.time_s == pytest.approx(explicit.time_s, rel=1e-12)


def test_hplsim_rejects_overcommitted_platform():
    plat = get_platform("bdw-local")        # 16 nodes
    cfg = HPLConfig(N=4096, nb=128, P=8, Q=8)
    with pytest.raises(ValueError, match="ranks"):
        HPLSim(cfg, plat)


def test_with_calibration_merges_and_applies():
    plat = get_platform("frontera")
    cal = plat.with_calibration({"bcast_bw_scale": 0.5})
    assert cal.fastsim().bcast_bw_scale == pytest.approx(0.5)
    assert cal.fastsim(calibrated=False).bcast_bw_scale == \
        plat.fastsim(calibrated=False).bcast_bw_scale
    # original spec untouched; round trip preserves the table
    assert plat.fastsim().bcast_bw_scale != pytest.approx(0.5) or \
        not plat.calibration
    assert Platform.from_dict(cal.to_dict()) == cal


# ------------------------------------------------- DES/fastsim agreement

@pytest.mark.parametrize("name", ALL_NAMES)
def test_cross_validation_des_vs_fastsim(name):
    """Both backends built from one spec must tell the same story:
    GFLOPS within 15% on a small config for every registry machine."""
    plat = get_platform(name)
    cfg = _small_cfg(plat)
    des = HPLSim(cfg, plat).run()
    prm = dataclasses.replace(plat.fastsim(), lookahead=0.0)
    fast = simulate_hpl_fast(cfg, prm)
    rel = abs(des.gflops - fast["gflops"]) / des.gflops
    assert rel < 0.15, (name, des.gflops, fast["gflops"], rel)


# -------------------------------------------------- calibration bridge

@pytest.mark.slow
def test_bridge_fits_contention_scales_to_des():
    from repro.platforms import fit_fastsim_to_des
    plat = get_platform("bdw-local")
    bridge = fit_fastsim_to_des(plat, steps=40)
    assert bridge.fit.loss <= bridge.fit.loss0 * 1.001
    cal = bridge.platform.calibration_dict
    assert set(cal) == {"bcast_bw_scale", "swap_bw_scale"}
    for v in cal.values():
        assert 0.05 < v < 20.0          # sane contention scales
    # the calibrated spec is serializable with its fitted table
    assert Platform.from_dict(bridge.platform.to_dict()) == bridge.platform


@pytest.mark.slow
def test_bridge_frontera_reproduces_table2_within_5pct():
    """Acceptance: fit_fastsim_to_des on Frontera's spec must reproduce
    Table 2's predicted GFLOPS within 5% of the uncalibrated path."""
    from repro.platforms import fit_fastsim_to_des
    plat = get_platform("frontera")
    cfg = plat.hpl_config()
    baseline = simulate_hpl_fast(cfg, plat.fastsim(calibrated=False))
    bridged = fit_fastsim_to_des(plat, steps=40)
    calibrated = simulate_hpl_fast(cfg, bridged.platform.fastsim())
    rel = abs(calibrated["gflops"] - baseline["gflops"]) \
        / baseline["gflops"]
    assert rel < 0.05, (baseline["gflops"], calibrated["gflops"], rel)


# ------------------------------------------------------ serving by name

def test_service_serves_platform_names():
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    geom = {"N": 2048, "nb": 128, "P": 2, "Q": 4}
    names = ["frontera", "pupmaya", "tpu-v5e-pod"]
    out = svc.predict_batch([
        WorkloadRequest(rid=i, workload="hpl", platform=name, params=geom)
        for i, name in enumerate(names)])
    assert set(out) == {0, 1, 2}
    for i, name in enumerate(names):
        plat = get_platform(name)
        expect = simulate_hpl_fast(plat.hpl_config(**geom), plat.fastsim())
        assert out[i]["time_s"] == pytest.approx(expect["time_s"],
                                                 rel=1e-6)
    # a platform-name request with no overrides serves the published run
    req = WorkloadRequest(rid=7, workload="hpl", platform="bdw-local")
    res = svc.predict_batch([req])
    plat = get_platform("bdw-local")
    expect = simulate_hpl_fast(plat.hpl_config(), plat.fastsim())
    assert res[7]["time_s"] == pytest.approx(expect["time_s"], rel=1e-6)


def test_service_rejects_unresolvable_request():
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    with pytest.raises(ValueError, match="needs a platform"):
        svc.submit(WorkloadRequest(rid=0, workload="hpl", params={
            "N": 512, "nb": 128, "P": 1, "Q": 1}))
    assert not svc._queue
