"""Metrics & telemetry subsystem (repro.obs, DESIGN.md §18).

Four contracts under test:

  * instrument semantics — counters/gauges/histograms/timers, keying,
    deterministic snapshots, JSON round-trip, merge algebra;
  * exporters — Prometheus text held to the exposition grammar by the
    repo's own validator, NDJSON run manifests;
  * the observe-only guarantee — instrumented runs are bit-identical to
    uninstrumented ones on every layer (DES engine, fastsim, stepsim,
    the serving front ends, the fleet path);
  * serving telemetry — every hardening path (retries, deadline
    fallbacks, rank-guard trips, isolated errors, dispatch failures)
    increments its counter, and one mixed wave surfaces all of them in
    both the Prometheus text and the manifest line.
"""
import json

import pytest

from repro.obs import (COUNT_BUCKETS, NULL_METRICS, MetricsRegistry,
                       global_metrics, manifest_record, merge_snapshots,
                       read_manifest, validate_prometheus_text)
from repro.obs.metrics import flatten_key, parse_key

HPL_SMALL = dict(N=1536, nb=128, P=2, Q=2, lookahead=0)
TF_SMALL = {"mesh": (2, 4), "num_layers": 2}


# ------------------------------------------------------------ instruments

def test_counter_gauge_histogram_basics():
    m = MetricsRegistry()
    c = m.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError, match=">= 0"):
        c.inc(-1)
    g = m.gauge("g")
    g.set(5)
    g.set(2)
    assert (g.value, g.max, g.min) == (2.0, 5.0, 2.0)
    h = m.histogram("h", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert h.counts == [1, 1, 1] and h.count == 3
    assert h.sum == 55.5 and (h.min, h.max) == (0.5, 50.0)
    assert h.mean == pytest.approx(18.5)
    assert 0.0 < h.quantile(0.5) <= 10.0


def test_histogram_bad_bounds_raise():
    from repro.obs import Histogram
    with pytest.raises(ValueError, match="ascending"):
        Histogram(bounds=(2.0, 1.0))
    with pytest.raises(ValueError, match="ascending"):
        Histogram(bounds=(1.0, 1.0))


def test_instruments_are_cached_and_keyed_by_labels():
    m = MetricsRegistry()
    assert m.counter("x", a="1") is m.counter("x", a="1")
    assert m.counter("x", a="1") is not m.counter("x", a="2")
    assert m.counter("x") is not m.counter("x", a="1")


def test_timer_records_elapsed():
    m = MetricsRegistry()
    with m.timer("span") as t:
        pass
    assert t.elapsed is not None and t.elapsed >= 0.0
    assert m.histogram("span").count == 1


def test_key_flatten_parse_round_trip():
    key = flatten_key("serve.latency", (("kind", "hpl"), ("zone", "a")))
    assert key == 'serve.latency{kind="hpl",zone="a"}'
    assert parse_key(key) == ("serve.latency",
                              (("kind", "hpl"), ("zone", "a")))
    assert parse_key("bare") == ("bare", ())


# ------------------------------------------- snapshots, JSON, merge

def _sample_registry():
    m = MetricsRegistry()
    m.counter("c", kind="x").inc(3)
    m.gauge("g").set(7)
    m.gauge("g").set(2)
    h = m.histogram("h", buckets=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    return m


def test_snapshot_is_deterministic_and_round_trips():
    a, b = _sample_registry(), _sample_registry()
    assert a.to_json() == b.to_json()          # equal histories, equal bytes
    back = MetricsRegistry.from_json(a.to_json())
    assert back.to_json() == a.to_json()


def test_merge_semantics():
    a, b = _sample_registry(), _sample_registry()
    a.merge(b)
    snap = a.snapshot()
    assert snap["counters"]['c{kind="x"}'] == 6.0      # counters sum
    g = snap["gauges"]["g"]
    assert g["max"] == 7.0 and g["min"] == 2.0         # extremes merge
    h = snap["histograms"]["h"]
    assert h["counts"] == [2, 2, 0] and h["count"] == 4
    assert h["sum"] == 11.0


def test_merge_rejects_mismatched_bounds():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.histogram("h", buckets=(1.0, 2.0)).observe(0.5)
    b.histogram("h", buckets=(1.0, 3.0)).observe(0.5)
    with pytest.raises(ValueError, match="bounds differ"):
        a.merge(b)


def test_merge_snapshots_commutes():
    a, b = _sample_registry().snapshot(), MetricsRegistry().snapshot()
    c = _sample_registry()
    c.counter("other").inc()
    c = c.snapshot()
    assert merge_snapshots(a, c) == merge_snapshots(c, a)
    assert merge_snapshots(a, b, c) == merge_snapshots(
        a, merge_snapshots(b, c))


def test_null_metrics_is_inert():
    n = NULL_METRICS
    assert not n.enabled
    n.counter("x").inc()
    n.gauge("x").set(1)
    n.histogram("x").observe(1.0)
    with n.timer("x"):
        pass
    assert n.snapshot() == {"counters": {}, "gauges": {},
                            "histograms": {}}
    assert n.to_prometheus() == ""


def test_global_metrics_hook_scopes_and_restores():
    from repro.obs import get_global_metrics
    assert get_global_metrics() is NULL_METRICS
    m = MetricsRegistry()
    with global_metrics(m):
        assert get_global_metrics() is m
    assert get_global_metrics() is NULL_METRICS


# ------------------------------------------------------------- exporters

def test_prometheus_export_passes_own_validator():
    m = _sample_registry()
    text = m.to_prometheus()
    samples = validate_prometheus_text(text)
    by_name = {}
    for name, labels, value in samples:
        by_name.setdefault(name, []).append((labels, value))
    assert by_name["c_total"] == [({"kind": "x"}, 3.0)]   # counter suffix
    assert ("g", [({}, 2.0)]) in by_name.items()
    assert by_name["g_peak"] == [({}, 7.0)]               # gauge peak
    les = [l["le"] for l, _ in by_name["h_bucket"]]
    assert les[-1] == "+Inf"                              # cumulative tail
    assert by_name["h_count"] == [({}, 2.0)]


def test_prometheus_validator_rejects_bad_text():
    with pytest.raises(ValueError, match="bad sample line"):
        validate_prometheus_text("9bad_name 1")
    with pytest.raises(ValueError, match="not cumulative"):
        validate_prometheus_text(
            'h_bucket{le="1"} 5\nh_bucket{le="+Inf"} 3\n')
    with pytest.raises(ValueError, match='le="\\+Inf"'):
        validate_prometheus_text('h_bucket{le="1"} 1\n')
    with pytest.raises(ValueError, match="!= _count"):
        validate_prometheus_text(
            'h_bucket{le="+Inf"} 3\nh_count 4\n')


def test_manifest_round_trip(tmp_path):
    from repro.obs import append_manifest
    m = _sample_registry()
    rec = manifest_record("bench", meta={"n": 3}, metrics=m)
    assert rec["manifest"] == 1 and rec["kind"] == "bench"
    assert rec["meta"] == {"n": 3}
    assert rec["metrics"] == m.snapshot()
    p = tmp_path / "runs.ndjson"
    l1 = append_manifest(p, "bench", meta={"n": 3}, metrics=m)
    l2 = append_manifest(p, "bench", meta={"n": 3},
                         metrics=_sample_registry())
    assert l1 == l2                       # equal runs, byte-equal lines
    recs = read_manifest(p)
    assert len(recs) == 2 and recs[0] == rec


# ------------------------------------------- bit-identity, layer by layer

def test_engine_metrics_do_not_perturb_hpl_des():
    from repro.core.apps.hpl import HPLConfig, HPLSim
    from repro.platforms import get_platform
    plat = get_platform("bdw-local")
    cfg = HPLConfig(**HPL_SMALL, bcast=plat.mpi.bcast)
    ref = HPLSim(cfg, plat).run()
    sim = HPLSim(cfg, plat)
    sim.engine.metrics = m = MetricsRegistry()
    res = sim.run()
    assert res.time_s == ref.time_s and res.events == ref.events
    snap = m.snapshot()
    assert snap["counters"]["engine.events"] == ref.events
    assert snap["counters"]["engine.runs"] == 1.0
    assert snap["gauges"]["engine.queue_depth_peak"]["max"] > 0
    assert snap["histograms"]["engine.events_per_s"]["count"] == 1


def test_engine_metrics_do_not_perturb_transformer_des():
    from repro.platforms import get_platform
    from repro.workloads import get_workload
    plat = get_platform("tpu-v5e-pod")
    wl = get_workload("transformer", **TF_SMALL)
    ref = wl.des_app(plat).run()
    app = wl.des_app(plat)
    app.engine.metrics = m = MetricsRegistry()
    res = app.run()
    assert res["step_s"] == ref["step_s"]
    assert res["events"] == ref["events"]
    assert m.snapshot()["counters"]["engine.events"] == ref["events"]


def test_engine_metrics_flush_on_deadline_path():
    from repro.core.apps.hpl import HPLConfig, HPLSim
    from repro.platforms import get_platform
    plat = get_platform("bdw-local")
    cfg = HPLConfig(**HPL_SMALL, bcast=plat.mpi.bcast)
    ref = HPLSim(cfg, plat).run()
    sim = HPLSim(cfg, plat)
    sim.engine.metrics = m = MetricsRegistry()
    sim.engine.set_wall_deadline(60.0)       # generous: runs to completion
    res = sim.run()
    assert res.time_s == ref.time_s and res.events == ref.events
    assert m.snapshot()["counters"]["engine.events"] == ref.events


def test_fastsim_sweep_metrics_observe_only():
    from repro.core.apps.hpl import HPLConfig
    from repro.core.fastsim import sweep_hpl
    from repro.platforms import get_platform
    plat = get_platform("frontera")
    # panel counts 14/15/16 share shape bucket 16: one batched group,
    # three live lanes padded to four
    cfgs = [HPLConfig(N=n, nb=128, P=2, Q=2, bcast=plat.mpi.bcast)
            for n in (1792, 1920, 2048)]
    prms = [plat.fastsim()] * len(cfgs)
    ref = sweep_hpl(cfgs, prms)
    m = MetricsRegistry()
    with global_metrics(m):
        res = sweep_hpl(cfgs, prms)
    assert [r["time_s"] for r in res] == [r["time_s"] for r in ref]
    c = m.snapshot()["counters"]
    hits = sum(v for k, v in c.items()
               if k.startswith("fastsim.compile_hits"))
    misses = sum(v for k, v in c.items()
                 if k.startswith("fastsim.compile_misses"))
    assert hits + misses >= 1            # the dispatch was recorded
    assert c["fastsim.lanes_live"] == 3.0
    assert c["fastsim.lanes_padded"] == 1.0           # padded to 4 lanes
    live, padded = c["fastsim.lanes_live"], c["fastsim.lanes_padded"]
    assert live / (live + padded) == pytest.approx(0.75)   # occupancy


def test_stepsim_sweep_metrics_observe_only():
    from repro.platforms import get_platform
    from repro.workloads import get_workload
    plat = get_platform("tpu-v5e-pod")
    wl = get_workload("transformer", **TF_SMALL)
    ref = wl.fastsim_model(plat).predict()
    m = MetricsRegistry()
    with global_metrics(m):
        res = wl.fastsim_model(plat).predict()
    assert res["step_s"] == ref["step_s"]
    c = m.snapshot()["counters"]
    assert (c.get('stepsim.compile_hits{bucket="step"}', 0)
            + c.get('stepsim.compile_misses{bucket="step"}', 0)) >= 1
    assert c["stepsim.lanes_live"] == 1.0


def test_serving_results_bit_identical_with_metrics_off():
    from repro.serve import PredictionService, WorkloadRequest

    def reqs():
        return [
            WorkloadRequest(rid=0, workload="hpl", platform="bdw-local",
                            params=dict(HPL_SMALL)),
            WorkloadRequest(rid=1, workload="transformer",
                            platform="tpu-v5e-pod",
                            params=dict(TF_SMALL)),
            WorkloadRequest(rid=2, workload="hpl", platform="bdw-local",
                            params=dict(HPL_SMALL), breakdown=True),
        ]

    on = PredictionService().predict_batch(reqs())
    off = PredictionService(metrics=NULL_METRICS).predict_batch(reqs())
    assert on == off


# ------------------------------------------------------ serving telemetry

def test_serve_wave_metrics_and_latency():
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    svc.predict_batch([
        WorkloadRequest(rid=i, workload="hpl", platform="bdw-local",
                        params=dict(HPL_SMALL)) for i in range(3)])
    snap = svc.metrics.snapshot()
    c = snap["counters"]
    assert c["serve.requests"] == 3.0
    assert c["serve.scenarios"] == 3.0
    assert c["serve.batches"] == 1.0 and c["serve.sweeps"] == 1.0
    assert snap["gauges"]["serve.queue_depth"]["max"] == 3.0
    assert snap["gauges"]["serve.queue_depth"]["value"] == 0.0
    ws = snap["histograms"]["serve.wave_size"]
    assert ws["count"] == 1 and ws["sum"] == 3.0
    assert ws["bounds"] == list(COUNT_BUCKETS)
    assert snap["histograms"]["serve.request_latency_s"]["count"] == 3


def test_acceptance_wave_retry_fallback_isolation_all_visible():
    # ISSUE 8 acceptance: ONE wave exercising a retry, a deadline
    # fallback, and an isolated error yields nonzero counters for each,
    # visible in the Prometheus text AND the NDJSON manifest.
    from repro.serve import PredictionService, WorkloadRequest
    from repro.workloads import HPLFastModel

    svc = PredictionService(backoff_s=0.001)
    orig = HPLFastModel.sweep_models.__func__
    state = {"n": 0}

    def flaky(cls, models, *, shard=False):
        state["n"] += 1
        if state["n"] == 1:
            raise RuntimeError("transient hiccup")
        return orig(cls, models, shard=shard)

    HPLFastModel.sweep_models = classmethod(flaky)
    try:
        out = svc.predict_batch(
            [WorkloadRequest(rid=0, workload="hpl", platform="bdw-local",
                             params=dict(HPL_SMALL)),
             WorkloadRequest(rid=1, workload="transformer",
                             platform="tpu-v5e-pod",
                             params=dict(TF_SMALL),
                             breakdown=True, timeout_s=1e-9),
             WorkloadRequest(rid=2, workload="hpl", platform="nope")],
            isolate_errors=True)
    finally:
        HPLFastModel.sweep_models = classmethod(orig)
    assert out[0]["status"] == "ok"
    assert out[1]["degraded"] and out[2]["status"] == "error"

    c = svc.metrics.snapshot()["counters"]
    for key in ("serve.retries", "serve.fallbacks",
                "serve.deadline_fallbacks", "serve.errors_isolated"):
        assert c[key] > 0, key

    samples = {name: value
               for name, labels, value in
               validate_prometheus_text(svc.prometheus())}
    assert samples["serve_retries_total"] > 0
    assert samples["serve_deadline_fallbacks_total"] > 0
    assert samples["serve_errors_isolated_total"] > 0

    rec = json.loads(svc.manifest())
    mc = rec["metrics"]["counters"]
    assert mc["serve.retries"] > 0
    assert mc["serve.deadline_fallbacks"] > 0
    assert mc["serve.errors_isolated"] > 0
    assert rec["meta"]["service"] == "PredictionService"
    assert rec["meta"]["stats"] == svc.stats


def test_rank_guard_trip_counter():
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    out = svc.predict_batch([WorkloadRequest(
        rid=0, workload="transformer", platform="syn-torus-fugaku-4k",
        breakdown=True, timeout_s=60.0)])
    assert out[0]["degraded"]
    c = svc.metrics.snapshot()["counters"]
    assert c["serve.rank_guard_trips"] == 1.0
    assert c["serve.fallbacks"] == 1.0
    assert "serve.deadline_fallbacks" not in c


def test_dispatch_failure_stamps_wave_and_keeps_queue_clean():
    # Satellite 1: resolve-all-before-enqueue extended to dispatch time.
    # A sweep that fails after retries stamps EVERY request in the wave
    # with an error result, re-raises, and leaves the queue clean — the
    # service stays reusable.
    from repro.serve import PredictionService, WorkloadRequest
    from repro.workloads import HPLFastModel

    svc = PredictionService(retries=0)
    orig = HPLFastModel.sweep_models.__func__

    def broken(cls, models, *, shard=False):
        raise RuntimeError("backend down")

    reqs = [WorkloadRequest(rid=0, workload="hpl", platform="bdw-local",
                            params=dict(HPL_SMALL)),
            WorkloadRequest(rid=1, workload="transformer",
                            platform="tpu-v5e-pod",
                            params=dict(TF_SMALL))]
    HPLFastModel.sweep_models = classmethod(broken)
    try:
        with pytest.raises(RuntimeError, match="backend down"):
            svc.predict_batch(reqs)
    finally:
        HPLFastModel.sweep_models = classmethod(orig)
    assert svc._queue == []
    for r in reqs:
        assert r.result["status"] == "error"
        assert r.result["error_type"] == "RuntimeError"
    c = svc.metrics.snapshot()["counters"]
    assert c["serve.dispatch_failures"] == 1.0
    # the service serves the next wave normally
    out = svc.predict_batch([WorkloadRequest(
        rid=9, workload="hpl", platform="bdw-local",
        params=dict(HPL_SMALL))])
    assert out[9]["time_s"] > 0


def test_hpl_by_name_wave_metric_counts():
    # two HPL requests by platform name are one wave of one sweep
    from repro.serve import PredictionService, WorkloadRequest
    names = ["frontera", "bdw-local"]
    svc = PredictionService()
    svc.predict_batch([
        WorkloadRequest(rid=i, workload="hpl", platform=n)
        for i, n in enumerate(names)])
    snap = svc.metrics.snapshot()
    c = snap["counters"]
    assert [c[k] for k in ("serve.requests", "serve.batches",
                           "serve.scenarios", "serve.sweeps")] == \
        [2.0, 1.0, 2.0, 1.0]
    h = snap["histograms"]
    assert h["serve.request_latency_s"]["count"] == 2
    assert h["serve.wave_size"]["sum"] == 2.0


def test_service_registries_merge_across_replicas():
    from repro.serve import PredictionService, WorkloadRequest
    svcs = [PredictionService() for _ in range(2)]
    for i, svc in enumerate(svcs):
        svc.predict_batch([WorkloadRequest(
            rid=i, workload="hpl", platform="bdw-local",
            params=dict(HPL_SMALL))])
    fleet = MetricsRegistry()
    for svc in svcs:
        fleet.merge(svc.metrics)
    assert fleet.snapshot()["counters"]["serve.requests"] == 2.0


# ------------------------------------------------------- fleet telemetry

def test_fleet_metrics_and_run_manifest(tmp_path):
    from repro.platforms import get_platform
    from repro.top500 import FleetTuning, predict_fleet
    plats = [get_platform("bdw-local"), get_platform("frontera")]
    tuning = FleetTuning(max_ranks=64)
    ref = predict_fleet(plats, tuning=tuning)
    m = MetricsRegistry()
    report = predict_fleet(plats, tuning=tuning, metrics=m)
    for e1, e2 in zip(ref.entries, report.entries):
        assert e1.predicted_tflops == e2.predicted_tflops   # observe-only
    snap = m.snapshot()
    c = snap["counters"]
    assert c["fleet.machines"] == 2.0
    phases = {parse_key(k)[1][0][1]
              for k in snap["histograms"] if k.startswith("fleet.phase")}
    assert phases == {"tune", "params", "bucket", "sweep", "report",
                      "calibrate"}
    assert any(k.startswith("fleet.calibration_factor")
               for k in snap["gauges"])

    p = tmp_path / "fleet.ndjson"
    report.run_manifest(p, campaign="unit")
    rec = read_manifest(p)[0]
    assert rec["kind"] == "fleet_run"
    assert rec["meta"]["machines"] == 2
    assert rec["meta"]["campaign"] == "unit"
    assert rec["metrics"]["counters"]["fleet.machines"] == 2.0
    # uninstrumented report still emits a (metrics-free) manifest line
    rec2 = json.loads(ref.run_manifest())
    assert rec2["meta"]["machines"] == 2 and "metrics" not in rec2


def test_predict_top500_counts_rows(tmp_path):
    from repro.serve import predict_top500
    from repro.top500 import FleetTuning
    csv = tmp_path / "list.csv"
    csv.write_text(
        "Rank,Processor,Total Cores,Interconnect,Rmax,Rpeak\n"
        "1,Xeon Gold 6148 20C 2.4GHz,40000,EDR,500,768\n"
        "2,Xeon Gold 6148 20C 2.4GHz,bogus,EDR,500,768\n",
        encoding="utf-8")
    m = MetricsRegistry()
    report = predict_top500(str(csv), tuning=FleetTuning(max_ranks=64),
                            calibrate=False, metrics=m)
    c = m.snapshot()["counters"]
    assert c["fleet.rows_parsed"] == 1.0
    assert c["fleet.rows_skipped"] == 1.0
    assert len(report.entries) == 1


# ------------------- manifest read hardening (campaign satellite)

def _torn_journal(tmp_path):
    """Two good lines, a blank, a non-object, and a torn tail — the
    shape a killed campaign run leaves behind."""
    from repro.obs.export import manifest_line
    path = tmp_path / "torn.ndjson"
    path.write_text(manifest_line("run", meta={"i": 0}) + "\n"
                    "\n"
                    + manifest_line("run", meta={"i": 1}) + "\n"
                    '["not", "an", "object"]\n'
                    '{"kind": "run", "meta": {"i": 2')
    return path


def test_read_manifest_lenient_skips_with_count(tmp_path):
    from repro.obs import read_manifest_report
    report = read_manifest_report(_torn_journal(tmp_path))
    assert [r["meta"]["i"] for r in report.records] == [0, 1]
    assert len(report) == 2 and list(report) == report.records
    # blank lines are never an error; the two corrupt lines are
    # counted with their 1-based line numbers and a reason each
    assert [lineno for lineno, _ in report.skipped] == [4, 5]
    assert "expected a JSON object" in report.skipped[0][1]


def test_read_manifest_lenient_list_form_unchanged(tmp_path):
    recs = read_manifest(_torn_journal(tmp_path))
    assert isinstance(recs, list) and len(recs) == 2


def test_read_manifest_strict_raises_with_location(tmp_path):
    path = _torn_journal(tmp_path)
    with pytest.raises(ValueError, match=r"line 4: expected a JSON "
                                         r"object, got list"):
        read_manifest(path, strict=True)
    from repro.obs.export import manifest_line
    clean = tmp_path / "clean.ndjson"
    clean.write_text(manifest_line("run", meta={"i": 0}) + "\n"
                     + manifest_line("run", meta={"i": 1}) + "\n")
    assert len(read_manifest(clean, strict=True)) == 2


def test_read_manifest_empty_and_blank_files(tmp_path):
    empty = tmp_path / "empty.ndjson"
    empty.write_text("")
    blank = tmp_path / "blank.ndjson"
    blank.write_text("\n\n\n")
    for p in (empty, blank):
        assert read_manifest(p, strict=True) == []
