"""Workload layer: registry + spec round trips, HPL/transformer parity
with the pre-layer plumbing, DES-vs-stepsim cross-validation on registry
platforms (the transformer mirror of test_platforms' HPL bound),
compile-once sweeps, the generic what-if grid, the workload-routing
prediction service, and the TOP500 DES-bridge calibration path."""
import dataclasses

import pytest

from repro.core.apps.hpl import HPLConfig, HPLSim
from repro.core.fastsim import simulate_hpl_fast
from repro.platforms import Platform, get_platform
from repro.workloads import (HPLWorkload, StepParams, TransformerWorkload,
                             Workload, WorkloadSpec, get_workload,
                             list_workloads, sweep_step, trace_count,
                             workload_from_spec)

TORUS_PLATFORMS = ("tpu-v5e-pod", "syn-torus-fugaku-4k", "syn-torus-bgq-8k")
SMALL = dict(mesh=(2, 4), num_layers=3)     # 8-rank DES probes


# ---------------------------------------------------------------- registry

def test_registry_lists_both_workloads():
    assert {"hpl", "transformer"} <= set(list_workloads())
    assert isinstance(get_workload("hpl"), HPLWorkload)
    assert isinstance(get_workload("transformer"), TransformerWorkload)


def test_registry_unknown_name_suggests_close_matches():
    with pytest.raises(KeyError, match="transformer"):
        get_workload("transformre")
    with pytest.raises(KeyError, match="registered"):
        get_workload("stencil")


def test_workload_from_spec_and_param_overrides():
    spec = WorkloadSpec.make("hpl", N=2048, nb=128, P=2, Q=4)
    wl = workload_from_spec(spec)
    assert isinstance(wl, HPLWorkload)
    assert wl.config(get_platform("bdw-local")) == HPLConfig(
        N=2048, nb=128, P=2, Q=4, bcast="1ring")
    wl2 = get_workload("hpl", spec=spec, Q=2)
    assert wl2.spec.get("Q") == 2 and wl2.spec.get("N") == 2048
    with pytest.raises(ValueError, match="kind"):
        TransformerWorkload(spec=spec)


# ------------------------------------------------------------ spec as data

def test_workload_spec_round_trip_and_normalization():
    s = WorkloadSpec.make("transformer", mesh=[4, 8], num_layers=6)
    assert s == WorkloadSpec.from_json(s.to_json())
    assert s == WorkloadSpec.from_dict(s.to_dict())
    # list/tuple params normalize equal, and specs hash
    assert s == WorkloadSpec.make("transformer", num_layers=6, mesh=(4, 8))
    assert hash(s) == hash(WorkloadSpec.from_json(s.to_json()))
    with pytest.raises(TypeError, match="JSON-safe"):
        WorkloadSpec.make("hpl", bad=object())


def test_workload_spec_hypothesis_round_trip():
    hypothesis = pytest.importorskip("hypothesis")
    import hypothesis.strategies as st
    from hypothesis import given, settings

    scalars = st.one_of(
        st.none(), st.booleans(), st.integers(-2**40, 2**40),
        st.floats(allow_nan=False, allow_infinity=False), st.text())
    values = st.one_of(scalars, st.lists(scalars, max_size=4))

    @settings(max_examples=50, deadline=None)
    @given(kind=st.text(min_size=1), name=st.text(),
           params=st.dictionaries(st.text(), values, max_size=6))
    def inner(kind, name, params):
        spec = WorkloadSpec.make(kind, name=name, **params)
        assert WorkloadSpec.from_json(spec.to_json()) == spec

    inner()


# ----------------------------------------------------- HPL extraction

def test_hpl_workload_matches_platform_plumbing():
    """The extracted workload must serve exactly what the HPL-specific
    path served: published run, spec-calibrated params."""
    plat = get_platform("tpu-v5e-pod")
    model = get_workload("hpl").fastsim_model(plat)
    direct = simulate_hpl_fast(plat.hpl_config(), plat.fastsim())
    assert model.predict()["time_s"] == pytest.approx(direct["time_s"],
                                                      rel=1e-9)
    res = get_workload("hpl").predict(plat)
    assert res["gflops"] == pytest.approx(direct["gflops"], rel=1e-9)


def test_hpl_workload_des_matches_hplsim():
    plat = get_platform("bdw-local")
    wl = get_workload("hpl", N=1536, nb=128, P=2, Q=4, lookahead=0)
    res = wl.predict_des(plat)
    direct = HPLSim(HPLConfig(N=1536, nb=128, P=2, Q=4, lookahead=0),
                    plat).run()
    assert res["time_s"] == pytest.approx(direct.time_s, rel=1e-12)


def test_hpl_workload_validates_capacity():
    wl = get_workload("hpl", N=4096, nb=128, P=64, Q=64)
    with pytest.raises(ValueError, match="ranks"):
        wl.validate(get_platform("bdw-local"))


# ------------------------------------------- transformer over platforms

def test_transformer_geometry_from_fabric():
    wl = get_workload("transformer")
    assert wl.geometry(get_platform("tpu-v5e-pod")) == ((16, 16), 1)
    assert wl.geometry(get_platform("syn-torus-fugaku-4k")) == ((256, 16), 1)
    assert wl.geometry(get_platform("syn-mp-2pod-v5e")) == ((16, 16), 2)
    with pytest.raises(ValueError, match="fat-tree"):
        wl.geometry(get_platform("frontera"))
    with pytest.raises(ValueError, match="rows, cols"):
        get_workload("transformer", mesh=[2, 4, 4]).geometry(
            get_platform("tpu-v5e-pod"))
    with pytest.raises(ValueError, match="chips"):
        get_workload("transformer", mesh=[64, 64]).validate(
            get_platform("tpu-v5e-pod"))


@pytest.mark.parametrize("name", TORUS_PLATFORMS)
def test_cross_validation_des_vs_stepsim(name):
    """Both transformer backends built from one spec must tell the same
    story — the workload mirror of the <15% HPL bound."""
    plat = get_platform(name)
    wl = get_workload("transformer", **SMALL)
    des = wl.predict_des(plat)
    fast = wl.predict(plat)
    rel = abs(des["step_s"] - fast["step_s"]) / des["step_s"]
    assert rel < 0.15, (name, des["step_s"], fast["step_s"], rel)


def test_cross_validation_multipod_gateway_model():
    """Cross-pod rings funnel through the pod gateway; the analytic
    contention model is approximate — hold it to 30% and to the right
    side (a second pod must cost time in both backends)."""
    plat = get_platform("syn-mp-2pod-v5e")
    wl = get_workload("transformer", **SMALL)
    des = wl.predict_des(plat)
    fast = wl.predict(plat)
    rel = abs(des["step_s"] - fast["step_s"]) / des["step_s"]
    assert rel < 0.30, (des["step_s"], fast["step_s"], rel)
    single = get_workload("transformer", pods=1, **SMALL).predict(plat)
    assert fast["step_s"] > single["step_s"]
    assert des["step_s"] > single["step_s"]


def test_transformer_end_to_end_acceptance():
    """ISSUE acceptance: the one-liner must run end to end."""
    model = get_workload("transformer").fastsim_model(
        get_platform("tpu-v5e-pod"))
    out = model.predict()
    assert out["step_s"] > 0 and 0 < out["mfu"] < 1
    assert out["tokens_per_s"] > 0


# ------------------------------------------------------ batched stepsim

def test_step_sweep_compiles_once_for_16_scenarios():
    """ISSUE acceptance: a single what-if sweep over the transformer
    workload compiles once across >= 16 scenarios."""
    model = get_workload("transformer").fastsim_model(
        get_platform("tpu-v5e-pod"))
    base = model.params
    grid = [dataclasses.replace(base,
                                link_bw=base.link_bw * (1 + 0.1 * i),
                                n_layers=float(2 + i),
                                flops_per_layer=base.flops_per_layer
                                * (1 + 0.05 * i))
            for i in range(18)]
    model.sweep(grid[:18])               # warm the (32,)-lane program
    c0 = trace_count()
    res = model.sweep(grid)
    assert trace_count() - c0 == 0       # fully cached
    assert len(res) == 18
    # cold-cache single compile for a fresh lane count
    c0 = trace_count()
    res2 = model.sweep([dataclasses.replace(g, mem_bw=g.mem_bw * 1.25)
                        for g in grid])
    assert trace_count() - c0 <= 1
    for r, r2 in zip(res, res2):
        assert r2["time_s"] <= r["time_s"] + 1e-12


def test_step_sweep_matches_singles():
    plat = get_platform("syn-torus-fugaku-4k")
    model = get_workload("transformer").fastsim_model(plat)
    base = model.params
    grid = [dataclasses.replace(base, link_bw=base.link_bw * s)
            for s in (0.5, 1.0, 2.0, 4.0)]
    batched = sweep_step(grid)
    for p, b in zip(grid, batched):
        single = sweep_step([p])[0]
        assert b["time_s"] == pytest.approx(single["time_s"], rel=1e-12)
    # monotone: more bandwidth never slows the step
    times = [b["time_s"] for b in batched]
    assert times == sorted(times, reverse=True)


def test_step_params_gradient_flows():
    jax = pytest.importorskip("jax")
    from repro.workloads import step_time_traced

    model = get_workload("transformer").fastsim_model(
        get_platform("tpu-v5e-pod"))

    def loss(scale):
        p = dataclasses.replace(model.params,
                                link_bw=model.params.link_bw * scale)
        return step_time_traced(p)

    with jax.enable_x64(True):
        g = jax.grad(loss)(1.0)
    assert g < 0                 # faster links -> shorter step


# ------------------------------------------------------ generic what-if

def test_whatif_grid_accepts_workloads_and_legacy_config():
    from repro.core.predict import whatif_grid
    plat = get_platform("tpu-v5e-pod")
    rows = whatif_grid(get_workload("transformer"), plat,
                       {"link_bw": [1.0, 2.0], "mem_bw": [1.0, 1.5]})
    assert len(rows) == 4
    assert rows[0]["speedup"] == pytest.approx(1.0, rel=1e-9)
    assert all(r["speedup"] >= 0.999 for r in rows)
    hrows = whatif_grid(get_workload("hpl"), plat, {"link_bw": [1.0, 2.0]})
    assert hrows[0]["speedup"] == pytest.approx(1.0, rel=1e-9)
    assert "gflops" in hrows[0]
    # legacy (cfg, params) form must behave identically to before
    cfg = plat.hpl_config()
    lrows = whatif_grid(cfg, plat.fastsim(), {"link_bw": [1.0, 2.0]})
    assert lrows[1]["time_s"] == pytest.approx(hrows[1]["time_s"], rel=1e-9)
    with pytest.raises(ValueError, match="platform"):
        whatif_grid(get_workload("hpl"), None, {"link_bw": [1.0]})


# -------------------------------------------------------------- serving

def test_prediction_service_routes_mixed_workloads():
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    out = svc.predict_batch([
        WorkloadRequest(rid=0, workload="hpl", platform="tpu-v5e-pod"),
        WorkloadRequest(rid=1, workload="transformer",
                        platform="tpu-v5e-pod"),
        WorkloadRequest(rid=2, workload="hpl", platform="frontera"),
    ])
    assert set(out) == {0, 1, 2}
    plat = get_platform("tpu-v5e-pod")
    assert out[0]["time_s"] == pytest.approx(
        get_workload("hpl").predict(plat)["time_s"], rel=1e-9)
    assert out[1]["step_s"] == pytest.approx(
        get_workload("transformer").predict(plat)["step_s"], rel=1e-9)
    # one wave, one sweep per workload family
    assert svc.stats["batches"] == 1 and svc.stats["sweeps"] == 2


def test_prediction_service_all_or_nothing_and_breakdown_guard():
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    with pytest.raises(KeyError, match="unknown platform"):
        svc.predict_batch([
            WorkloadRequest(rid=0, workload="hpl", platform="tpu-v5e-pod"),
            WorkloadRequest(rid=1, workload="hpl", platform="nope"),
        ])
    assert not svc._queue and svc.stats["requests"] == 0
    with pytest.raises(ValueError, match="max_des_ranks"):
        svc.predict_batch([WorkloadRequest(
            rid=0, workload="transformer", platform="syn-torus-fugaku-4k",
            breakdown=True)])        # default mesh = 4096 DES ranks
    out = svc.predict_batch([WorkloadRequest(
        rid=7, workload="transformer", platform="tpu-v5e-pod",
        params={"mesh": [2, 4], "num_layers": 2}, breakdown=True)])
    assert out[7]["breakdown"]["n_ranks"] == 8   # trace summary attached
    assert svc.predict_batch([]) == {}


# ------------------------------------------- TOP500 DES-bridge path

def test_calibrate_against_des_records_provenance():
    from repro.top500 import (calibrate_against_des, infer_platforms,
                              load_sample, predict_fleet)
    rows = load_sample()[:3]
    plats = infer_platforms(rows)
    res = calibrate_against_des(plats, steps=6)
    assert len(res.platforms) == len(plats)
    for plat in res.platforms:
        cal = plat.calibration_dict
        # the audit trail's applied table matches what was baked in
        fam = next(f for f, t in res.tables.items() if t == cal)
        assert res.donors[fam] and res.fits[fam]
        assert {"bcast_bw_scale", "swap_bw_scale"} <= set(cal)
        assert all(0.01 < v < 50.0 for v in cal.values())
        prov = plat.provenance_dict["calibration"]
        assert prov.startswith("des-bridge:")
        # calibrated spec stays serializable data
        assert Platform.from_dict(plat.to_dict()) == plat
    # the DES-bridge record survives a later residual pass
    report = predict_fleet(res.platforms, calibrate=True)
    for e in report.entries:
        assert e.platform.provenance_dict["calibration"].startswith(
            "des-bridge:")


def test_family_factor_path_records_provenance():
    from repro.top500 import infer_platforms, load_sample, predict_fleet
    rows = load_sample()[:6]
    report = predict_fleet(infer_platforms(rows), calibrate=True)
    for e in report.entries:
        assert e.platform.provenance_dict["calibration"] == "family-factor"
