"""Spans on the profiler's clock (repro.obs, DESIGN.md §18).

  * an enabled registry's timer annotates the profiler trace's host
    plane; the null timer records nothing;
  * the compiled call is split into prepare / launch / wait spans whose
    histograms add up to the dispatch wall, and sweep answers stay
    bitwise equal with the registry on and off in every dispatch mode;
  * the serving front end leaves one span of each kind per wave;
  * the programs and the panel step's phases carry stable names;
  * an enabled registry counts exactly under concurrent threads.
"""
import sys
import threading
import time

import numpy as np
import pytest

from repro.obs import NULL_METRICS, MetricsRegistry, global_metrics

# A and C share the (16, 2, 2) shape bucket at different N, so together
# they are one batch-mode call; B is alone in (16, 2, 3)
GEOMS = {"A": (1792, 2, 2), "B": (1920, 2, 3), "C": (2048, 2, 2)}


def _cfg(name):
    from repro.core.apps.hpl import HPLConfig
    from repro.platforms import get_platform
    N, P, Q = GEOMS[name]
    return HPLConfig(N=N, nb=128, P=P, Q=Q,
                     bcast=get_platform("frontera").mpi.bcast)


def _params(n):
    import dataclasses

    from repro.platforms import get_platform
    base = get_platform("frontera").fastsim()
    return [dataclasses.replace(base, link_bw=base.link_bw * (1 + i / 4))
            for i in range(n)]


def _host_span_names(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(trace_dir.glob("**/*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    return {ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


def test_enabled_timer_lands_on_the_profiler_host_plane(tmp_path):
    import jax

    from repro.core.fastsim import sweep_hpl
    m = MetricsRegistry()
    cfg, prms = _cfg("C"), _params(2)
    sweep_hpl(cfg, prms)                          # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with m.timer("test.enabled_s"):
            time.sleep(0.001)
        with NULL_METRICS.timer("test.null_s"):
            time.sleep(0.001)
        with global_metrics(m):
            sweep_hpl(cfg, prms)
    finally:
        jax.profiler.stop_trace()
    names = _host_span_names(tmp_path)
    assert "test.enabled" in names
    assert "test.null" not in names
    assert {"fastsim.prepare", "fastsim.launch", "fastsim.wait"} <= names
    assert m.histogram("test.enabled_s").count == 1


def test_null_timer_records_nothing():
    t = NULL_METRICS.timer("x_s", span="x")
    assert t is NULL_METRICS.timer("y_s")         # one shared no-op
    with t as held:
        pass
    assert held.elapsed is None
    assert NULL_METRICS.snapshot() == {"counters": {}, "gauges": {},
                                       "histograms": {}}


def test_launch_plus_wait_is_the_dispatch_wall():
    from repro.core.fastsim import simulate_hpl_fast, sweep_hpl
    cfgs, prms = [_cfg("A"), _cfg("C")], _params(2)
    sweep_hpl(cfgs, prms)                         # warm: hits only below
    simulate_hpl_fast(_cfg("C"), prms[0])
    for run in (lambda: sweep_hpl(cfgs, prms),
                lambda: simulate_hpl_fast(_cfg("C"), prms[0])):
        m = MetricsRegistry()
        with global_metrics(m):
            run()
        h = m.snapshot()["histograms"]
        launch, wait = h["fastsim.launch_s"], h["fastsim.wait_s"]
        wall = h["fastsim.dispatch_wall_s"]
        assert launch["count"] == wait["count"] == wall["count"] == 1
        assert launch["sum"] + wait["sum"] == pytest.approx(
            wall["sum"], rel=1e-12)
        assert launch["sum"] > 0 and wait["sum"] > 0


def test_prepare_is_one_observation_per_sweep_on_hits_only():
    from repro.core import fastsim
    cfgs, prms = [_cfg("A"), _cfg("C"), _cfg("C")], _params(3)
    fastsim.sweep_hpl(cfgs, prms)
    m = MetricsRegistry()
    with global_metrics(m):
        fastsim.sweep_hpl(cfgs, prms)             # a params and a single call
        fastsim._compiled.cache_clear()
        try:
            fastsim.sweep_hpl(cfgs, prms)         # compiles: not observed
        finally:
            fastsim._compiled.cache_clear()
    h = m.snapshot()["histograms"]
    assert h["fastsim.prepare_s"]["count"] == 1
    assert h["fastsim.launch_s"]["count"] == 2


@pytest.mark.parametrize("mode", ["params", "batch", "forced", "single",
                                  "step"])
def test_sweep_answers_bitwise_equal_with_metrics_on_and_off(mode):
    from repro.core.fastsim import simulate_hpl_fast, sweep_hpl
    from repro.platforms import get_platform
    from repro.workloads import get_workload

    def answers():
        if mode == "params":
            return [r["time_s"] for r in sweep_hpl(_cfg("C"), _params(3))]
        if mode == "batch":
            return [r["time_s"] for r in sweep_hpl(
                [_cfg("A"), _cfg("B"), _cfg("C")], _params(3))]
        if mode == "forced":
            return [r["time_s"] for r in sweep_hpl(
                [_cfg("A"), _cfg("B")], _params(2), bucket=(32, 4, 4))]
        if mode == "single":
            return [simulate_hpl_fast(_cfg("B"), _params(1)[0])["time_s"]]
        wl = get_workload("transformer", mesh=(2, 4), num_layers=2)
        return [wl.fastsim_model(get_platform("tpu-v5e-pod")).predict()[
            "step_s"]]

    off = answers()
    m = MetricsRegistry()
    with global_metrics(m):
        on = answers()
    assert np.asarray(on).tobytes() == np.asarray(off).tobytes()
    prefix = "stepsim" if mode == "step" else "fastsim"
    assert m.histogram(f"{prefix}.launch_s").count == (
        2 if mode == "batch" else 1)


def test_service_wave_leaves_one_span_of_each_kind():
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    svc.predict_batch([WorkloadRequest(rid=i, workload="hpl",
                                       platform="bdw-local",
                                       params=dict(N=1536, nb=128, P=2,
                                                   Q=2))
                       for i in range(2)])
    h = svc.metrics.snapshot()["histograms"]
    for span in ("resolve", "flush", "dispatch", "assemble"):
        assert h[f"serve.{span}_s"]["count"] == 1, span


def test_programs_and_panel_phases_carry_stable_names():
    """Lowered at buckets no sweep produces (7 panels, 3 x 5; 3 lanes),
    so no other test's compile-cache counts move."""
    import jax

    from repro.core import fastsim
    from repro.workloads import stepsim
    prm = fastsim._f64_params(_params(1)[0])
    with jax.enable_x64(True):
        for mode in ("single", "params", "batch"):
            lanes = 1 if mode == "single" else 3
            p = prm if mode == "single" else fastsim._stack_params(
                [prm] * lanes, range(lanes))
            g = [np.int64(v) for v in (1792, 128, 2, 3)]
            if mode == "batch":
                g = [np.full(lanes, v) for v in g]
            text = fastsim._compiled(7, 3, 5, mode).lower(*g, p).as_text(
                debug_info=True)
            assert f"module @jit_hpl_recurrence_{mode}" in text
            for phase in ("tables", "fact", "bcast", "swap", "update",
                          "lookahead"):
                assert f"hpl.{phase}/" in text, (mode, phase)
        sp = stepsim._stack_step_params(
            [stepsim.StepParams(peak_flops=1e12, gemm_eff=0.5, mem_bw=1e9,
                                mem_eff=0.8, link_bw=1e9,
                                phase_latency=1e-6)] * 3, range(3))
        text = stepsim._compiled().lower(sp).as_text()
    assert "module @jit_transformer_step" in text


def test_enabled_registry_is_exact_under_threads():
    """Threads share one registry's instruments and create new ones
    concurrently; with a short switch interval a lost read-modify-write
    would show in the totals."""
    m = MetricsRegistry()
    n_threads, n_obs = 8, 3000

    def work(t):
        c, h = m.counter("t.count"), m.histogram("t.hist", (0.5, 1.0))
        g = m.gauge("t.gauge")
        for i in range(n_obs):
            c.inc()
            m.counter("t.labelled", k=str(i % 5)).inc(2.0)
            h.observe(0.25)
            g.set(t)
            with m.timer("t.span_s", span="t.span"):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    snap = m.snapshot()
    total = n_threads * n_obs
    assert snap["counters"]["t.count"] == total
    assert sum(v for k, v in snap["counters"].items()
               if k.startswith("t.labelled")) == 2.0 * total
    h = snap["histograms"]["t.hist"]
    assert h["count"] == total and h["counts"] == [total, 0, 0]
    assert h["sum"] == 0.25 * total
    assert snap["histograms"]["t.span_s"]["count"] == total
    assert snap["gauges"]["t.gauge"]["max"] == n_threads - 1
