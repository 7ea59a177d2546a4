"""Trace subsystem: recorder invariants, Chrome export schema, critical
path, determinism, and the zero-perturbation overhead contract."""
import json
import math

import pytest

from repro.core.apps.hpl import HPLConfig, HPLSim
from repro.core.apps.transformer import (LayerWork, StepWorkload,
                                         TransformerStepSim)
from repro.core.engine import Engine
from repro.core.hardware.node import local_node
from repro.core.hardware.topology import FatTreeTwoLevel
from repro.trace import (NULL_RECORDER, REQUIRED_KEYS, critical_path,
                         rank_breakdown, validate_chrome_events)

REL = 1e-9      # float tolerance for interval-sum identities


def _traced_hpl(N=1024, nb=128, P=2, Q=4, **kw):
    node = local_node()
    topo = FatTreeTwoLevel(max(P * Q, 16), 4, 2, link_bw=100e9 / 8)
    cfg = HPLConfig(N=N, nb=nb, P=P, Q=Q, **kw)
    sim = HPLSim(cfg, node, topo, trace=True)
    return sim, sim.run()


# ------------------------------------------------------------ contract
def test_trace_off_is_null_recorder_and_bit_identical():
    """trace=False costs nothing and trace=True perturbs nothing: both
    runs produce the exact same simulated time and event count."""
    node = local_node()
    cfg = HPLConfig(N=1024, nb=128, P=2, Q=4)

    def run(trace):
        topo = FatTreeTwoLevel(16, 4, 2, link_bw=100e9 / 8)
        return HPLSim(cfg, node, topo, trace=trace)

    off = run(False)
    assert off.trace is NULL_RECORDER
    assert not off.trace.enabled
    r_off = off.run()
    assert r_off.trace is None
    r_on = run(True).run()
    assert r_on.time_s == r_off.time_s          # bit-identical
    assert r_on.events == r_off.events
    assert r_on.trace is not None and r_on.trace.enabled


def test_traced_runs_are_deterministic():
    """Regression (same-timestamp tie-breaking + ordered flow sets): two
    fresh identical runs produce identical traces and results."""
    sims = []
    for _ in range(2):
        sim, res = _traced_hpl()
        sims.append((res, [(s.rank, s.cat, s.name, s.t0, s.t1)
                           for s in sim.trace.spans]))
    (res_a, spans_a), (res_b, spans_b) = sims
    assert res_a.time_s == res_b.time_s
    assert res_a.events == res_b.events
    assert spans_a == spans_b


# ----------------------------------------------------------- breakdown
def test_rank_breakdown_sums_to_makespan():
    sim, res = _traced_hpl()
    bd = rank_breakdown(sim.trace)
    assert set(bd) == set(range(sim.cfg.n_ranks))
    for r, acc in bd.items():
        assert acc["total"] == res.time_s
        assert acc["compute"] >= 0 and acc["comm"] >= 0
        assert acc["idle"] >= -REL * res.time_s, (r, acc)
        s = acc["compute"] + acc["comm"] + acc["idle"]
        assert s == pytest.approx(res.time_s, rel=REL), (r, acc)


def test_phase_and_collective_attribution():
    sim, res = _traced_hpl()
    s = sim.trace.summary()
    assert {"panel_fact", "panel_bcast", "row_swap",
            "trailing_update"} <= set(s["phases"])
    assert all(v > 0 for v in s["phases"].values())
    assert "barrier" in s["collectives"]          # pivot-sync collective
    ncalls = s["collectives"]["barrier"]["calls"]
    assert ncalls == sim.cfg.n_panels * sim.cfg.P  # one per panel per col rank


# ------------------------------------------------------- critical path
def test_critical_path_le_makespan_hpl():
    sim, res = _traced_hpl()
    cp = critical_path(sim.trace)
    assert cp.length_s <= res.time_s * (1 + REL)
    assert cp.length_s > 0.5 * res.time_s          # explains most of the run
    assert cp.spans[0].t0 <= cp.spans[-1].t0       # ordered start -> finish


def test_critical_path_equals_makespan_for_serial_chain():
    eng = Engine(trace=True)
    tr = eng.trace

    def proc():
        for i, dur in enumerate([0.5, 0.25, 1.0, 0.125]):
            tr.compute(0, f"step{i}", dur)
            yield dur
    eng.spawn(proc())
    makespan = eng.run_all()
    cp = critical_path(tr)
    assert cp.length_s == pytest.approx(makespan, rel=1e-12)
    assert len(cp.spans) == 4
    bd = rank_breakdown(tr)
    assert bd[0]["compute"] == pytest.approx(makespan, rel=1e-12)
    assert bd[0]["idle"] == pytest.approx(0.0, abs=1e-12)


def test_critical_path_follows_send_recv_edge():
    """Two ranks: r1 computes, sends to r0 which waited idle; the path
    must route through r1's work, not r0's idleness."""
    from repro.core.hardware.network import Network
    from repro.core.simmpi import SimMPI
    eng = Engine(trace=True)
    topo = FatTreeTwoLevel(16, 4, 2, link_bw=1e9)
    mpi = SimMPI(eng, Network(eng, topo), 2)
    tr = eng.trace

    def r0():
        yield from mpi.recv(1, 0, tag="x")
        tr.compute(0, "after", 1e-4)
        yield 1e-4

    def r1():
        tr.compute(1, "work", 5e-3)
        yield 5e-3
        yield from mpi.send(1, 0, 4 * 1024 * 1024, tag="x")
    eng.spawn(r0())
    eng.spawn(r1())
    makespan = eng.run_all()
    cp = critical_path(tr)
    names = [(s.rank, s.name) for s in cp.spans]
    assert (1, "work") in names            # crossed to the sender's rank
    assert (0, "after") in names
    assert cp.length_s <= makespan * (1 + REL)
    assert cp.length_s > 0.95 * makespan   # chain is essentially serial


# ---------------------------------------------------------- chrome json
def test_chrome_export_schema_and_roundtrip(tmp_path):
    sim, res = _traced_hpl()
    path = tmp_path / "trace.json"
    doc = sim.trace.to_chrome_json(str(path))
    validate_chrome_events(doc)
    on_disk = json.loads(path.read_text())
    assert on_disk["traceEvents"] == doc["traceEvents"]
    evs = doc["traceEvents"]
    for ev in evs:
        for k in REQUIRED_KEYS:
            assert k in ev, ev
    phs = {ev["ph"] for ev in evs}
    assert {"M", "X", "b", "e"} <= phs
    # one thread_name per rank, async slices begin<=end and balance
    names = [ev for ev in evs if ev["name"] == "thread_name"]
    assert len(names) == sim.cfg.n_ranks
    begins = [ev for ev in evs if ev["ph"] == "b"]
    ends = {(ev["cat"], ev["id"]): ev for ev in evs if ev["ph"] == "e"}
    assert len(begins) == len(ends)
    for b in begins:
        e = ends[(b["cat"], b["id"])]
        assert b["ts"] <= e["ts"]
    # complete events nest within the run and carry sane durations
    for ev in evs:
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
            assert 0 <= ev["ts"] <= res.time_s * 1e6 * (1 + REL)


def test_validate_chrome_events_rejects_bad_docs():
    with pytest.raises(ValueError):
        validate_chrome_events({})
    with pytest.raises(ValueError):
        validate_chrome_events({"traceEvents": [{"ph": "X", "ts": 0}]})
    with pytest.raises(ValueError):
        validate_chrome_events({"traceEvents": [
            {"ph": "X", "ts": "zero", "pid": 0, "tid": 0, "name": "n",
             "dur": 1}]})


# --------------------------------------------------------- transformer
def test_transformer_trace_phases_and_invariants():
    wl = StepWorkload(
        layers=[LayerWork(1e-3, [("all-reduce", 1 << 20, "model")])] * 3,
        tail_collectives=[("all-reduce", 1 << 22, "data")])
    sim = TransformerStepSim(wl, mesh=(4, 4), trace=True)
    out = sim.run()
    s = sim.trace.summary()
    assert {"layer0", "layer1", "layer2", "tail"} <= set(s["phases"])
    assert "all-reduce" in s["collectives"]
    assert s["critical_path_s"] <= out["step_s"] * (1 + REL)
    for acc in rank_breakdown(sim.trace).values():
        assert acc["compute"] + acc["comm"] <= out["step_s"] * (1 + REL)
    # untraced run unchanged
    out_off = TransformerStepSim(wl, mesh=(4, 4)).run()
    assert out_off["step_s"] == out["step_s"]
    assert out_off["events"] == out["events"]


# ------------------------------------------------------------- wiring
def test_platform_des_trace_flag_flows_through():
    from repro.platforms import get_platform
    plat = get_platform("bdw-local")
    cfg = plat.hpl_config(N=512, nb=64, P=2, Q=2)
    stack = plat.des(trace=True)
    assert stack.trace
    res = HPLSim(cfg, stack).run()
    assert res.trace is not None and res.trace.enabled
    assert len(res.trace.spans) > 0
    # default stays off
    assert HPLSim(cfg, plat).engine.trace is NULL_RECORDER


def test_service_breakdown_option():
    pytest.importorskip("jax")
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    geom = {"N": 512, "nb": 64, "P": 2, "Q": 2}
    out = svc.predict_batch([
        WorkloadRequest(rid=0, workload="hpl", platform="bdw-local",
                        params=geom),
        WorkloadRequest(rid=1, workload="hpl", platform="bdw-local",
                        params=geom, breakdown=True)])
    assert "breakdown" not in out[0]
    bd = out[1]["breakdown"]
    assert bd["makespan_s"] > 0
    assert bd["compute_frac"] + bd["comm_frac"] + bd["idle_frac"] \
        == pytest.approx(1.0, rel=1e-6)
    assert bd["critical_path_s"] <= bd["makespan_s"] * (1 + REL)
    assert "panel_bcast" in bd["phases"]
    assert svc.stats["des_breakdowns"] == 1


def test_service_breakdown_guards():
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService(max_des_ranks=4)
    with pytest.raises(ValueError, match="max_des_ranks"):
        svc.submit(WorkloadRequest(
            rid=0, workload="hpl", platform="bdw-local",
            params={"N": 512, "nb": 64, "P": 4, "Q": 4}, breakdown=True))
    assert not svc._queue


# Hypothesis property tests over random geometries live in
# tests/test_trace_properties.py (module-level importorskip would skip
# this whole file on hypothesis-less containers).
