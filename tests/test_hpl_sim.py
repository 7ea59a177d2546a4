"""HPL application model: numroc correctness, DES vs fastsim agreement,
and the paper's headline predictions (Table II band)."""
import dataclasses
import functools

import numpy as np
import pytest

from repro.core.apps.hpl import HPLConfig, HPLSim, numroc
from repro.core.fastsim import FastSimParams, simulate_hpl_fast
from repro.core.hardware.node import (frontera_node, local_node,
                                      pupmaya_node)
from repro.core.hardware.topology import FatTreeTwoLevel


def test_numroc_partitions_completely():
    for n, nb, p in [(1000, 32, 4), (4096, 128, 3), (999, 7, 5)]:
        total = sum(numroc(n, nb, i, p) for i in range(p))
        assert total == n, (n, nb, p, total)


def test_des_fastsim_cross_validation():
    node = local_node()
    topo = FatTreeTwoLevel(16, 4, 2, link_bw=100e9 / 8)
    for (N, nb, P, Q) in [(2048, 128, 4, 4), (4096, 128, 2, 8)]:
        cfg = HPLConfig(N=N, nb=nb, P=P, Q=Q)
        des = HPLSim(cfg, node, topo).run()
        prm = dataclasses.replace(
            FastSimParams.from_node(node, link_bw=100e9 / 8), lookahead=0.0)
        fast = simulate_hpl_fast(cfg, prm)
        rel = abs(des.time_s - fast["time_s"]) / des.time_s
        assert rel < 0.15, (N, nb, P, Q, des.time_s, fast["time_s"])


def test_gflops_below_peak_and_sane():
    node = local_node()
    topo = FatTreeTwoLevel(16, 4, 2, link_bw=100e9 / 8)
    cfg = HPLConfig(N=4096, nb=128, P=4, Q=4)
    res = HPLSim(cfg, node, topo).run()
    agg_peak = 16 * node.peak_flops / 1e9
    assert 0.01 * agg_peak < res.gflops < agg_peak


@pytest.mark.slow
def test_table2_frontera_prediction_band():
    """Paper Table II: Frontera Rmax 23,516 TF; paper's sim says 22,566
    (-4%).  Our prediction must land within 10% of the reported Rmax."""
    cfg = HPLConfig(N=9_282_848, nb=384, P=88, Q=91)
    prm = FastSimParams.from_node(frontera_node(), link_bw=100e9 / 8)
    res = simulate_hpl_fast(cfg, prm)
    assert abs(res["tflops"] - 23516) / 23516 < 0.10, res["tflops"]


@pytest.mark.slow
def test_table2_pupmaya_prediction_band():
    cfg = HPLConfig(N=4_748_928, nb=384, P=59, Q=72)
    prm = FastSimParams.from_node(pupmaya_node(), link_bw=100e9 / 8)
    res = simulate_hpl_fast(cfg, prm)
    assert abs(res["tflops"] - 7484) / 7484 < 0.10, res["tflops"]


def test_hplconfig_validation_rejects_nonsense():
    with pytest.raises(ValueError):
        HPLConfig(N=0, nb=128, P=2, Q=2)
    with pytest.raises(ValueError):
        HPLConfig(N=1024, nb=0, P=2, Q=2)
    with pytest.raises(ValueError):
        HPLConfig(N=1024, nb=128, P=0, Q=2)
    with pytest.raises(ValueError):
        HPLConfig(N=1024, nb=128, P=2, Q=-1)
    with pytest.raises(ValueError):
        HPLConfig(N=1024, nb=128, P=2, Q=2, bcast="ring9")
    with pytest.raises(ValueError):
        HPLConfig(N=1024, nb=128, P=2, Q=2, lookahead=3)


def test_partial_trailing_panel_is_modeled():
    """N=1000, nb=96: 10 full panels + one 40-wide panel.  Both
    simulators must charge for the extra panel (not silently drop it)
    and still agree with each other."""
    node = local_node()
    topo = FatTreeTwoLevel(16, 4, 2, link_bw=100e9 / 8)
    prm = dataclasses.replace(
        FastSimParams.from_node(node, link_bw=100e9 / 8), lookahead=0.0)

    cfg_partial = HPLConfig(N=1000, nb=96, P=2, Q=2)
    cfg_floor = HPLConfig(N=960, nb=96, P=2, Q=2)
    assert cfg_partial.n_panels == 11 and cfg_floor.n_panels == 10

    des_partial = HPLSim(cfg_partial, node, topo).run()
    des_floor = HPLSim(cfg_floor, node, topo).run()
    fast_partial = simulate_hpl_fast(cfg_partial, prm)
    fast_floor = simulate_hpl_fast(cfg_floor, prm)

    # the trailing 40 columns cost strictly positive time in both worlds
    assert des_partial.time_s > des_floor.time_s
    assert fast_partial["time_s"] > fast_floor["time_s"]
    # and the two fidelities still tell the same story
    rel = abs(des_partial.time_s - fast_partial["time_s"]) \
        / des_partial.time_s
    assert rel < 0.20, (des_partial.time_s, fast_partial["time_s"])


def test_whatif_network_upgrade_small_gain():
    """Paper §V: doubling fabric bandwidth buys only a few percent."""
    cfg = HPLConfig(N=1_000_000, nb=384, P=32, Q=32)
    node = frontera_node()
    r100 = simulate_hpl_fast(cfg, FastSimParams.from_node(
        node, link_bw=100e9 / 8))
    r200 = simulate_hpl_fast(cfg, FastSimParams.from_node(
        node, link_bw=200e9 / 8))
    gain = r200["tflops"] / r100["tflops"] - 1
    assert 0.0 <= gain < 0.15


# ------------------------------------------- several ranks on one node

def _node_share(R):
    """``local_node`` scaled R times per node: every rank keeps the same
    share of peak, memory bandwidth and cores, so only the network sees
    that R ranks share a node."""
    node = local_node()
    return dataclasses.replace(node, peak_flops=node.peak_flops * R,
                               mem_bw=node.mem_bw * R, cores=node.cores * R)


# (P, Q, R, pmap): R in {2, 4, 6} under both mappings, with R < P, R = P
# and P | R for "col" and the mirror cases for "row"
NODE_CASES = [(4, 4, 2, "col"), (2, 8, 2, "col"), (8, 4, 4, "col"),
              (4, 8, 4, "col"), (2, 8, 4, "col"), (12, 4, 6, "col"),
              (6, 6, 6, "col"), (2, 12, 6, "col"), (3, 8, 6, "col"),
              (4, 4, 2, "row"), (8, 2, 2, "row"), (4, 8, 4, "row"),
              (8, 4, 4, "row"), (8, 2, 4, "row"), (4, 12, 6, "row"),
              (6, 6, 6, "row"), (4, 3, 6, "row")]
NODE_N, NODE_NB = 16384, 128
# DES against the fast model on these cases: each within the 15% the
# registry cross-validation allows one rank per node (test_platforms).
# The node terms price each hop and swap round as the DES does; what is
# left is the recurrence's own gap at one rank per node (-9% .. +9% on
# these grids), which the node terms scale with the hop costs they
# change (the DES holds a sender until its rendezvous transfer ends).
NODE_TOL = 0.15


@functools.lru_cache(maxsize=1)
def _node_runs():
    """{case: (DES, node-aware, node-blind) seconds}; the fast model in
    two forced-bucket sweeps (one program each), lookahead off as in the
    DES."""
    from repro.core.fastsim import sweep_hpl
    des, aware, blind = [], [], []
    for P, Q, R, pmap in NODE_CASES:
        topo = FatTreeTwoLevel(-(-P * Q // R), 4, 2, link_bw=100e9 / 8)
        cfg = HPLConfig(N=NODE_N, nb=NODE_NB, P=P, Q=Q, pmap=pmap)
        sim = HPLSim(cfg, _node_share(R), topo, ranks_per_node=R)
        des.append(sim.run().time_s)
        # a message inside a node pays the MPI overhead and base latency
        prm = dataclasses.replace(FastSimParams.from_node(
            _node_share(R), link_bw=100e9 / 8, ranks_per_node=R,
            intra_latency=sim.mpi.overhead + topo.base_latency),
            lookahead=0.0)
        aware.append((cfg, prm))
        blind.append((cfg, dataclasses.replace(prm, ranks_per_node=1)))
    bucket = (NODE_N // NODE_NB, 12, 12)
    t_aware = [r["time_s"] for r in sweep_hpl(*zip(*aware), bucket=bucket)]
    t_blind = [r["time_s"] for r in sweep_hpl(*zip(*blind), bucket=bucket)]
    return {c: v for c, v in zip(NODE_CASES, zip(des, t_aware, t_blind))}


@pytest.mark.parametrize("case", NODE_CASES,
                         ids=lambda c: "{}x{}-R{}-{}".format(*c))
def test_node_aware_fast_model_tracks_des(case):
    des, aware, _ = _node_runs()[case]
    assert abs(aware - des) / des < NODE_TOL, (case, des, aware)


def test_node_aware_gap_is_smaller_than_node_blind():
    runs = _node_runs().values()
    gap_aware = np.mean([abs(a - d) / d for d, a, _ in runs])
    gap_blind = np.mean([abs(b - d) / d for d, _, b in runs])
    assert gap_aware < gap_blind, (gap_aware, gap_blind)


@pytest.mark.parametrize("P,Q,R,pmap", [(6, 4, 4, "col"), (4, 6, 4, "row"),
                                        (3, 5, 2, "col"), (5, 3, 2, "row")])
def test_fast_model_refuses_nodes_that_hold_no_block(P, Q, R, pmap):
    """Where a node's ranks do not form a block of the grid, the hop and
    swap prices do not hold: the fast model raises, the DES runs it."""
    from repro.core.fastsim import sweep_hpl
    cfg = HPLConfig(N=1024, nb=128, P=P, Q=Q, pmap=pmap)
    prm = FastSimParams.from_node(_node_share(R), link_bw=100e9 / 8,
                                  ranks_per_node=R)
    with pytest.raises(ValueError, match="block"):
        simulate_hpl_fast(cfg, prm)
    with pytest.raises(ValueError, match="block"):
        sweep_hpl([cfg, cfg], [prm, prm])
    topo = FatTreeTwoLevel(-(-P * Q // R), 4, 2, link_bw=100e9 / 8)
    res = HPLSim(cfg, _node_share(R), topo, ranks_per_node=R).run()
    assert res.time_s > 0 and not res.failed


def test_fast_model_refuses_the_long_broadcast():
    """The fast path runs the 1ring broadcast only; 'long' is the DES's."""
    cfg = HPLConfig(N=2048, nb=128, P=2, Q=8, bcast="long")
    prm = FastSimParams.from_node(local_node(), link_bw=100e9 / 8)
    with pytest.raises(ValueError, match="DES"):
        simulate_hpl_fast(cfg, prm)
    topo = FatTreeTwoLevel(16, 4, 2, link_bw=100e9 / 8)
    assert HPLSim(cfg, local_node(), topo).run().time_s > 0


@pytest.mark.parametrize("pmap", ["col", "row"])
def test_des_process_mapping_places_ranks(pmap):
    """rank_of and position are inverse; "col" puts rank q*P + p at
    (p, q), "row" rank p*Q + q; an unknown mapping is refused."""
    cfg = HPLConfig(N=1024, nb=128, P=3, Q=4, pmap=pmap)
    for r in range(cfg.n_ranks):
        assert cfg.rank_of(*cfg.position(r)) == r
    assert cfg.position(5) == ((1, 1) if pmap == "row" else (2, 1))
    with pytest.raises(ValueError, match="pmap"):
        HPLConfig(N=1024, nb=128, P=2, Q=2, pmap="diag")
