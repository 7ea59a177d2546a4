"""Fault-injection subsystem: spec round-trips, seeded bit-identical
replay, faults=None purity, DES injection through both workloads,
DES-vs-fastsim cross-validation, service hardening, and the ft layer's
thin-consumer rewiring (ISSUE 6 acceptance scenarios)."""
import dataclasses
import json
import random

import pytest

from repro.faults import (FASTSIM_KINDS, FAULT_KINDS, Fault, FaultSpec,
                          NO_FAULTS, as_fault_spec)
from repro.platforms import get_platform
from repro.workloads import get_workload

HPL_SMALL = dict(N=1536, nb=128, P=2, Q=4, lookahead=0)
TF_SMALL = dict(mesh=(2, 4), num_layers=3)

# ISSUE 6 acceptance scenario: one straggler chip at 0.5x speed plus
# two-ish degraded links (seeded 5% of the fabric at half bandwidth)
ACCEPTANCE = (FaultSpec.straggler(rank=1, slowdown=2.0, seed=7)
              + FaultSpec.degraded_links(0.05, factor=0.5, seed=7))


# ------------------------------------------------------------- spec data

def test_fault_spec_json_roundtrip():
    spec = FaultSpec(
        faults=(Fault("straggler", rank=3, factor=2.5, start=0.1),
                Fault("fail_stop", node=2),
                Fault("link_degrade", link_frac=0.1, factor=0.25),
                Fault("link_flap", node=1, factor=0.5, period=0.01,
                      duty=0.3, cycles=5),
                Fault("latency_jitter", sigma=0.4)),
        seed=42, name="kitchen-sink")
    assert FaultSpec.from_json(spec.to_json()) == spec
    assert FaultSpec.from_dict(json.loads(spec.to_json())) == spec
    # dict / JSON-string forms normalize through as_fault_spec
    assert as_fault_spec(spec.to_dict()) == spec
    assert as_fault_spec(spec.to_json()) == spec
    # hashable, like every other spec in the repo
    assert hash(spec) == hash(FaultSpec.from_json(spec.to_json()))


def test_fault_spec_fuzzed_roundtrip():
    """Seeded-random fuzz of the JSON round-trip (stdlib stand-in for
    the hypothesis property in test_faults_properties.py)."""
    rng = random.Random(1234)
    for _ in range(200):
        kind = rng.choice(FAULT_KINDS)
        kw = dict(start=rng.uniform(0, 10), duration=rng.uniform(0, 5))
        if kind == "straggler":
            kw.update(rank=rng.randrange(64), factor=rng.uniform(0.1, 8))
        elif kind == "fail_stop":
            kw.update(rank=rng.randrange(64))
        elif kind in ("link_degrade", "link_flap"):
            kw.update(link_frac=rng.uniform(0.01, 1.0),
                      factor=rng.uniform(0.05, 1.0))
            if kind == "link_flap":
                kw.update(period=rng.uniform(1e-4, 1.0),
                          duty=rng.uniform(0.05, 0.95),
                          cycles=rng.randrange(1, 20))
        else:
            kw.update(sigma=rng.uniform(0.01, 0.99))
        spec = FaultSpec(faults=(Fault(kind, **kw),),
                         seed=rng.randrange(1 << 31))
        assert FaultSpec.from_json(spec.to_json()) == spec


def test_fault_validation_rejects_bad_records():
    with pytest.raises(ValueError, match="kind"):
        Fault("meteor_strike")
    with pytest.raises(ValueError, match="rank"):
        Fault("straggler")
    with pytest.raises(ValueError, match="factor"):
        Fault("straggler", rank=0, factor=0.0)
    with pytest.raises(ValueError, match="rank or a node"):
        Fault("fail_stop")
    with pytest.raises(ValueError, match="link_frac"):
        Fault("link_degrade", factor=0.5)
    with pytest.raises(ValueError, match="capacity"):
        Fault("link_degrade", link_frac=0.5, factor=2.0)
    with pytest.raises(ValueError, match="finite"):
        Fault("link_flap", link_frac=0.5, factor=0.5, period=0.1, cycles=0)
    with pytest.raises(ValueError, match="sigma"):
        Fault("latency_jitter", sigma=0.0)


def test_as_fault_spec_normalization():
    assert as_fault_spec(None) is None
    assert as_fault_spec(NO_FAULTS) is None        # empty spec == no faults
    spec = FaultSpec.straggler(rank=0)
    assert as_fault_spec(spec) is spec
    with pytest.raises(TypeError, match="faults must be"):
        as_fault_spec(42)


def test_fault_spec_combinators():
    spec = ACCEPTANCE
    assert len(spec.faults) == 2
    assert spec.seed == 7
    assert [f.kind for f in spec.faults] == ["straggler", "link_degrade"]
    assert spec.fastsim_supported()
    assert not (spec + FaultSpec.fail_stop(rank=0)).fastsim_supported()
    assert set(FASTSIM_KINDS) < set(FAULT_KINDS)


# ------------------------------------------------- DES purity and replay

def test_faults_none_bit_identical_hpl():
    wl = get_workload("hpl", **HPL_SMALL)
    plat = get_platform("bdw-local")
    base = wl.predict_des(plat)
    for faults in (None, NO_FAULTS, FaultSpec()):
        again = wl.predict_des(plat, faults=faults)
        assert again["time_s"] == base["time_s"]       # bit-identical
        assert again["events"] == base["events"]


def test_faults_none_bit_identical_transformer():
    wl = get_workload("transformer", **TF_SMALL)
    plat = get_platform("tpu-v5e-pod")
    base = wl.predict_des(plat)
    again = wl.predict_des(plat, faults=None)
    assert again["time_s"] == base["time_s"]
    assert again["events"] == base["events"]


def test_seeded_replay_bit_identical():
    """The same seeded spec — link sampling AND jitter draws — replays
    to the exact same simulated history, twice."""
    spec = (FaultSpec.degraded_links(0.2, factor=0.4, seed=99)
            + FaultSpec(faults=(Fault("latency_jitter", sigma=0.3),))
            + FaultSpec(faults=(Fault("link_flap", link_frac=0.1,
                                      factor=0.5, period=1e-3,
                                      duty=0.5, cycles=3),)))
    wl = get_workload("hpl", **HPL_SMALL)
    plat = get_platform("bdw-local")
    a = wl.predict_des(plat, faults=spec)
    b = wl.predict_des(plat, faults=spec)
    assert a["time_s"] == b["time_s"]
    assert a["events"] == b["events"]
    # and a different seed gives a different degraded platform
    other = dataclasses.replace(spec, seed=100)
    c = wl.predict_des(plat, faults=other)
    assert c["time_s"] != a["time_s"]


# ------------------------------------- acceptance scenario, both workloads

@pytest.mark.parametrize("kind,plat_name,params", [
    ("hpl", "bdw-local", HPL_SMALL),
    ("transformer", "tpu-v5e-pod", TF_SMALL),
])
def test_acceptance_scenario_des_with_trace_markers(kind, plat_name, params):
    from repro.trace import to_chrome_json, validate_chrome_events
    wl = get_workload(kind, **params)
    plat = get_platform(plat_name)
    healthy = wl.predict_des(plat)
    app = wl.des_app(plat, trace=True, faults=ACCEPTANCE)
    app.run()
    trace = app.engine.trace
    assert app.engine.now > healthy["time_s"]        # faults cost time
    # fault spans on the dedicated track, excluded from breakdowns
    summ = trace.summary()
    names = {f["name"] for f in summ["faults"]}
    assert {"straggler", "link_degrade"} <= names
    doc = to_chrome_json(trace)
    validate_chrome_events(doc)
    tids = {e["args"]["name"] for e in doc["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "thread_name"}
    assert "faults" in tids


def test_straggler_cross_validation_des_vs_fastsim():
    """The fastsim straggler mapping tracks the DES within the repo's
    15% cross-validation band (gate calibrated across geometries)."""
    plat = get_platform("bdw-local")
    for (P, Q) in [(2, 4), (4, 4)]:
        wl = get_workload("hpl", N=1536, nb=128, P=P, Q=Q, lookahead=0)
        spec = FaultSpec.straggler(rank=1, slowdown=2.0)
        des = wl.predict_des(plat, faults=spec)
        fast = wl.predict(plat, faults=spec)
        rel = abs(des["time_s"] - fast["time_s"]) / des["time_s"]
        assert rel < 0.15, (P, Q, des["time_s"], fast["time_s"])


def test_transformer_straggler_fastsim_near_exact():
    """Symmetric mesh + ring syncs: the step time IS the straggler's
    chain, so the stepsim mapping is essentially exact."""
    wl = get_workload("transformer", **TF_SMALL)
    plat = get_platform("tpu-v5e-pod")
    spec = FaultSpec.straggler(rank=3, slowdown=3.0)
    des = wl.predict_des(plat, faults=spec)
    fast = wl.predict(plat, faults=spec)
    rel = abs(des["time_s"] - fast["time_s"]) / des["time_s"]
    assert rel < 0.05, (des["time_s"], fast["time_s"])


def test_acceptance_scenario_crossvalidates():
    wl = get_workload("hpl", **HPL_SMALL)
    plat = get_platform("bdw-local")
    des = wl.predict_des(plat, faults=ACCEPTANCE)
    fast = wl.predict(plat, faults=ACCEPTANCE)
    rel = abs(des["time_s"] - fast["time_s"]) / des["time_s"]
    assert rel < 0.15, (des["time_s"], fast["time_s"])


# ------------------------------------------------------------ fail-stop

def test_fail_stop_hpl_reports_partial_run():
    wl = get_workload("hpl", **HPL_SMALL)
    plat = get_platform("bdw-local")
    out = wl.predict_des(plat, faults=FaultSpec.fail_stop(rank=2, at=1e-4))
    assert out["failed"] and out["gflops"] == 0.0
    assert 0 <= out["n_finished"] < 8


def test_fail_stop_transformer_reports_partial_run():
    wl = get_workload("transformer", **TF_SMALL)
    plat = get_platform("tpu-v5e-pod")
    out = wl.predict_des(plat, faults=FaultSpec.fail_stop(rank=0))
    assert out["failed"] and out["n_finished"] < 8


def test_fastsim_rejects_des_only_kinds():
    from repro.faults.fastsim import apply_faults
    wl = get_workload("hpl", **HPL_SMALL)
    params = get_platform("bdw-local").fastsim()
    with pytest.raises(ValueError, match="fail_stop"):
        apply_faults(params, FaultSpec.fail_stop(rank=0))
    with pytest.raises(ValueError, match="DES-only"):
        apply_faults(params, FaultSpec(faults=(
            Fault("link_degrade", node=3, factor=0.5),)))
    with pytest.raises(ValueError, match="fail_stop"):
        wl.predict(get_platform("bdw-local"),
                   faults=FaultSpec.fail_stop(rank=0))


# ------------------------------------------------------ batched sweeps

def test_sweep_faults_one_compile_fault_grid():
    from repro.core.fastsim import trace_count
    from repro.faults.fastsim import sweep_faults
    wl = get_workload("hpl", **HPL_SMALL)
    plat = get_platform("bdw-local")
    specs = [FaultSpec.straggler(rank=1, slowdown=s)
             for s in (1.5, 2.0, 4.0)]
    t0 = trace_count()
    out = sweep_faults(wl, plat, specs)
    assert trace_count() - t0 <= 1          # whole fault grid, one trace
    assert len(out) == 4                    # healthy lane prepended
    assert out[0]["slowdown_vs_healthy"] == pytest.approx(1.0)
    slows = [r["slowdown_vs_healthy"] for r in out[1:]]
    assert all(s >= 1.0 for s in slows)
    assert slows == sorted(slows)           # worse straggler, worse run


# ------------------------------------------------------ serving hardening

def test_service_requests_carry_faults():
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    out = svc.predict_batch([
        WorkloadRequest(rid=0, workload="hpl", platform="bdw-local",
                        params=dict(HPL_SMALL)),
        WorkloadRequest(rid=1, workload="hpl", platform="bdw-local",
                        params=dict(HPL_SMALL), faults=ACCEPTANCE),
    ])
    assert out[1]["time_s"] > out[0]["time_s"]


def test_service_deadline_falls_back_to_fastsim():
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    out = svc.predict_batch([WorkloadRequest(
        rid=0, workload="transformer", platform="tpu-v5e-pod",
        params={"mesh": [4, 8], "num_layers": 8},
        breakdown=True, timeout_s=1e-9)])
    r = out[0]
    assert r["degraded"] and "breakdown" not in r
    assert r["fallback_reason"].startswith(("deadline_exceeded",
                                            "wall_deadline"))
    assert "time_s" in r                     # the fastsim answer stands
    assert svc.stats["fallbacks"] == 1


def test_service_rank_guard_fallback_only_with_timeout():
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    # strict default: reject (PR 5 contract, unchanged)
    with pytest.raises(ValueError, match="max_des_ranks"):
        svc.predict_batch([WorkloadRequest(
            rid=0, workload="transformer", platform="syn-torus-fugaku-4k",
            breakdown=True)])
    assert not svc._queue and svc.stats["requests"] == 0
    # budgeted request: degrade to the fastsim answer instead
    out = svc.predict_batch([WorkloadRequest(
        rid=1, workload="transformer", platform="syn-torus-fugaku-4k",
        breakdown=True, timeout_s=60.0)])
    assert out[1]["degraded"]
    assert out[1]["fallback_reason"].startswith("max_des_ranks")
    assert "time_s" in out[1]


def test_service_isolates_per_request_errors():
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    # default stays all-or-nothing (PR 4/5 contract)
    with pytest.raises(KeyError, match="unknown platform"):
        svc.predict_batch([
            WorkloadRequest(rid=0, workload="hpl", platform="tpu-v5e-pod"),
            WorkloadRequest(rid=1, workload="hpl", platform="nope"),
        ])
    assert not svc._queue and svc.stats["requests"] == 0
    # isolation: bad rids error out, good rids serve
    out = svc.predict_batch([
        WorkloadRequest(rid=0, workload="hpl", platform="tpu-v5e-pod"),
        WorkloadRequest(rid=1, workload="hpl", platform="nope"),
        WorkloadRequest(rid=2, workload="transformer",
                        platform="tpu-v5e-pod"),
    ], isolate_errors=True)
    assert out[1]["status"] == "error"
    assert out[1]["error_type"] == "KeyError"
    assert "unknown platform" in out[1]["error"]
    assert out[0]["status"] == "ok" and "time_s" in out[0]
    assert out[2]["status"] == "ok"
    assert not svc._queue and svc.stats["errors"] == 1
    # an all-failed (then empty) wave leaves the queue clean
    out = svc.predict_batch(
        [WorkloadRequest(rid=9, workload="hpl", platform="nope")],
        isolate_errors=True)
    assert out[9]["status"] == "error" and not svc._queue
    assert svc.predict_batch([], isolate_errors=True) == {}
    assert svc.predict_batch([]) == {}


def test_service_retries_transient_backend_errors():
    from repro.serve import PredictionService, WorkloadRequest
    from repro.workloads.hpl import HPLFastModel
    orig = HPLFastModel.sweep_models.__func__
    calls = {"n": 0}

    def flaky(cls, models, *, shard=False):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient backend glitch")
        return orig(cls, models, shard=shard)

    HPLFastModel.sweep_models = classmethod(flaky)
    try:
        svc = PredictionService(backoff_s=1e-4)
        out = svc.predict_batch([WorkloadRequest(
            rid=0, workload="hpl", platform="tpu-v5e-pod")])
        assert "time_s" in out[0]
        assert calls["n"] == 3 and svc.stats["retries"] == 2
        # exhausted retries surface the error (bounded, not infinite)
        calls["n"] = -100
        with pytest.raises(RuntimeError, match="transient"):
            svc.predict_batch([WorkloadRequest(
                rid=1, workload="hpl", platform="tpu-v5e-pod")])
    finally:
        HPLFastModel.sweep_models = classmethod(orig)
    # scenario errors are never retried
    svc2 = PredictionService()
    with pytest.raises(KeyError):
        svc2.predict_batch([WorkloadRequest(rid=0, workload="hpl",
                                            platform="nope")])
    assert svc2.stats["retries"] == 0


def test_service_does_not_retry_xla_errors():
    # a refused compile or a device OOM fails the same way every time:
    # it surfaces on the first attempt, not after retries and backoff
    import jax
    from repro.serve import PredictionService, WorkloadRequest
    from repro.workloads.hpl import HPLFastModel
    orig = HPLFastModel.sweep_models.__func__
    calls = {"n": 0}

    def oom(cls, models, *, shard=False):
        calls["n"] += 1
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    HPLFastModel.sweep_models = classmethod(oom)
    try:
        svc = PredictionService(backoff_s=1e-4)
        with pytest.raises(jax.errors.JaxRuntimeError, match="EXHAUSTED"):
            svc.predict_batch([WorkloadRequest(
                rid=0, workload="hpl", platform="tpu-v5e-pod")])
    finally:
        HPLFastModel.sweep_models = classmethod(orig)
    assert calls["n"] == 1 and svc.stats["retries"] == 0


# ------------------------------------------------------------ ft layer

def test_simulate_fault_impact_generic():
    from repro.ft import simulate_fault_impact
    out = simulate_fault_impact("transformer", "tpu-v5e-pod",
                                FaultSpec.straggler(rank=0, slowdown=3.0))
    assert out["backend"] == "fastsim"
    assert out["blowup"] > 1.0
    assert out["verdict"] in ("evict", "tolerate")
    des = simulate_fault_impact(
        get_workload("transformer", **TF_SMALL), "tpu-v5e-pod",
        FaultSpec.fail_stop(rank=3), des=True)
    assert des["failed"] and des["verdict"] == "restart"
    assert des["blowup"] == float("inf")


def test_restart_plan_for_faults():
    from repro.ft import restart_plan_for_faults
    spec = FaultSpec.fail_stop(rank=18) + FaultSpec.fail_stop(node=1)
    plan = restart_plan_for_faults(spec, global_batch=1792, resume_step=500,
                                   old_mesh=(16, 16), ranks_per_node=4)
    assert plan.new_mesh == (14, 16)         # rows 0 (node 1) and 1 (rank 18)
    assert plan.per_device_batch_new == 128
    assert "evicted dp rows [0, 1]" in plan.notes
    with pytest.raises(ValueError, match="no.*fail_stop|fail_stop"):
        restart_plan_for_faults(FaultSpec.straggler(rank=0), global_batch=8,
                                resume_step=0, old_mesh=(4, 4))
    with pytest.raises(ValueError, match="surviving"):
        restart_plan_for_faults(FaultSpec.fail_stop(rank=0), global_batch=8,
                                resume_step=0, old_mesh=(1, 4))


def test_engine_wall_deadline():
    from repro.core.engine import Engine, SimWallDeadline

    def ticker(eng):
        while True:
            yield 1e-6

    eng = Engine()
    eng.spawn(ticker(eng))
    eng.set_wall_deadline(0.05)
    with pytest.raises(SimWallDeadline, match="wall"):
        eng.run_all()
    # and without a deadline the same engine construct runs fine
    eng2 = Engine()

    def finite():
        for _ in range(10):
            yield 1e-6
    eng2.spawn(finite())
    eng2.run_all()
    assert eng2.now == pytest.approx(1e-5)


def test_process_error_context():
    from repro.core.engine import Engine, ProcessError

    def boom():
        yield 1e-3
        raise KeyError("lost rendezvous")

    eng = Engine()
    eng.spawn(boom(), name="rank 7")
    with pytest.raises(ProcessError, match="rank 7") as ei:
        eng.run_all()
    assert ei.value.sim_time == pytest.approx(1e-3)
    assert isinstance(ei.value.__cause__, KeyError)
