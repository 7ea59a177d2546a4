"""Serving-throughput layer (repro.serve.cache + predict, DESIGN.md §20).

Contracts under test:

  * content addressing — the key is a digest of the resolved scenario
    tuple; any field change in spec/platform/faults/regions/breakdown
    misses; equal scenarios in different notations collide;
  * hit/miss bit-identity — a cache hit is byte-identical to the miss
    that populated it, modulo the ``cached=True`` provenance stamp;
  * LRU bounds — eviction is oldest-first and hits refresh recency;
  * invalidation — re-registering (or unregistering) a platform name
    drops every entry derived from it;
  * coalescing — duplicate in-flight keys dispatch exactly once (one
    sweep, one live lane) and fan identical results back out;
  * error hygiene — failed dispatches and degraded answers are never
    inserted into the cache;
  * warm pool — ``svc.warm`` precompiles the sweep buckets so the first
    real wave pays zero compiles (asserted via the §18 trace counters);
  * sharding — the single-device fallback is bitwise-identical to the
    unsharded path, and a forced multi-device run agrees bitwise too.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import pytest

from repro.platforms import get_platform, register, unregister
from repro.serve import (PredictionService, ResultCache, WorkloadRequest,
                         as_result_cache, request_key)
from repro.serve.cache import platform_digest, spec_digest


def _req(rid, **kw):
    kw.setdefault("workload", "hpl")
    kw.setdefault("platform", "frontera")
    kw.setdefault("params", {"N": 1536})
    return WorkloadRequest(rid=rid, **kw)


# -------------------------------------------------------- key semantics

def test_key_is_content_addressed_and_fully_sensitive():
    from repro.faults import FaultSpec
    from repro.scale import RegionSpec
    from repro.workloads import get_workload
    wl = get_workload("hpl", N=2048).spec
    plat = get_platform("frontera")
    base = request_key(wl, plat)
    # equal scenario -> equal key, regardless of how it was spelled
    assert request_key(get_workload("hpl", N=2048).spec, plat) == base
    # any field change anywhere misses
    assert request_key(get_workload("hpl", N=2049).spec, plat) != base
    assert request_key(wl, dataclasses.replace(plat, name="other")) != base
    assert request_key(wl, plat,
                       faults=FaultSpec.straggler(rank=0)) != base
    assert request_key(wl, plat, regions=12) != base
    assert request_key(wl, plat, breakdown=True) != base
    # notation-independence: int regions == the equivalent RegionSpec,
    # and a fault dict == the FaultSpec it normalizes to
    assert request_key(wl, plat, regions=12) == \
        request_key(wl, plat, regions=RegionSpec(panels=12, warmup=2))
    f = FaultSpec.straggler(rank=1, slowdown=2.0)
    import json
    assert request_key(wl, plat, faults=f) == \
        request_key(wl, plat, faults=json.loads(f.to_json()))


def test_digests_are_stable_across_equal_instances():
    plat = get_platform("frontera")
    assert platform_digest(plat) == platform_digest(
        dataclasses.replace(plat))
    from repro.workloads import get_workload
    assert spec_digest(get_workload("hpl", N=4096).spec) == \
        spec_digest(get_workload("hpl", N=4096).spec)


def test_as_result_cache_normalization():
    assert as_result_cache(None) is None
    assert as_result_cache(False) is None
    assert isinstance(as_result_cache(True), ResultCache)
    assert as_result_cache(7).max_entries == 7
    rc = ResultCache()
    assert as_result_cache(rc) is rc
    with pytest.raises(TypeError):
        as_result_cache("big")
    with pytest.raises(ValueError):
        ResultCache(max_entries=0)


# ------------------------------------------------------- hit/miss paths

def test_hit_is_bit_identical_to_miss_modulo_stamp():
    svc = PredictionService(cache=True)
    miss = svc.predict_batch([_req(0)])[0]
    hit = svc.predict_batch([_req(1)])[1]
    assert hit.pop("cached") is True
    assert "cached" not in miss
    assert hit == miss
    assert svc.stats["cache_hits"] == 1 and svc.stats["cache_misses"] == 1


def test_breakdown_hits_skip_the_des_but_carry_the_breakdown():
    svc = PredictionService(cache=True)
    miss = svc.predict_batch(
        [_req(0, platform="bdw-local", breakdown=True)])[0]
    assert "breakdown" in miss and svc.stats["des_breakdowns"] == 1
    hit = svc.predict_batch(
        [_req(1, platform="bdw-local", breakdown=True)])[1]
    assert hit["breakdown"] == miss["breakdown"]
    assert svc.stats["des_breakdowns"] == 1      # DES ran exactly once


def test_hit_payload_mutation_does_not_poison_the_cache():
    svc = PredictionService(cache=True)
    svc.predict_batch([_req(0)])
    first = svc.predict_batch([_req(1)])[1]
    first["time_s"] = -1.0
    again = svc.predict_batch([_req(2)])[2]
    assert again["time_s"] != -1.0


def test_lru_eviction_is_oldest_first_and_hits_refresh():
    rc = ResultCache(max_entries=2)
    rc.put("a", {"v": 1})
    rc.put("b", {"v": 2})
    assert rc.keys() == ["a", "b"]
    assert rc.get("a") == {"v": 1}       # refreshes "a"
    rc.put("c", {"v": 3})                # evicts "b", the LRU entry
    assert rc.keys() == ["a", "c"]
    assert rc.get("b") is None
    assert rc.stats()["evictions"] == 1


def test_service_cache_respects_max_entries():
    svc = PredictionService(cache=1)
    svc.predict_batch([_req(0, params={"N": 1536})])
    svc.predict_batch([_req(1, params={"N": 1920})])
    assert len(svc.cache) == 1
    # the first scenario was evicted: asking again is a miss
    svc.predict_batch([_req(2, params={"N": 1536})])
    assert svc.stats["cache_hits"] == 0


# --------------------------------------------------------- invalidation

def test_platform_reregistration_invalidates_by_name():
    plat = dataclasses.replace(get_platform("frontera"),
                               name="cachetest-inval")
    register(plat)
    try:
        svc = PredictionService(cache=True)
        svc.predict_batch([_req(0, platform="cachetest-inval")])
        assert len(svc.cache) == 1
        register(plat, overwrite=True)           # re-registration event
        assert len(svc.cache) == 0
        assert svc.cache.stats()["invalidations"] == 1
        # entries from other platforms survive
        svc.predict_batch([_req(1, platform="frontera"),
                           _req(2, platform="cachetest-inval")])
        assert len(svc.cache) == 2
        unregister(["cachetest-inval"])          # unregister drops too
        assert len(svc.cache) == 1
    finally:
        unregister(["cachetest-inval"])


# ----------------------------------------------------------- coalescing

def test_duplicate_in_flight_keys_dispatch_exactly_once():
    from repro.obs import global_metrics
    svc = PredictionService(cache=True)
    with global_metrics(svc.metrics):     # route fastsim counters here
        out = svc.predict_batch([_req(i) for i in range(8)])
    assert svc.stats["sweeps"] == 1 and svc.stats["coalesced"] == 7
    snap = svc.metrics.snapshot()["counters"]
    # ONE live lane went through the sweep engine for all 8 requests
    assert snap.get("fastsim.lanes_live") == 1
    assert len({repr(sorted(r.items())) for r in out.values()}) == 1


def test_coalescing_preserves_per_request_results_on_mixed_waves():
    svc = PredictionService(cache=True)
    reqs = [_req(0, params={"N": 1536}), _req(1, params={"N": 1920}),
            _req(2, params={"N": 1536}), _req(3, params={"N": 1920})]
    out = svc.predict_batch(reqs)
    assert out[0] == out[2] and out[1] == out[3]
    assert out[0]["time_s"] != out[1]["time_s"]
    assert svc.stats["sweeps"] == 1 and svc.stats["coalesced"] == 2


# -------------------------------------------------------- error hygiene

def test_dispatch_failure_caches_nothing_and_stamps_unserved(monkeypatch):
    svc = PredictionService(cache=True, retries=0)
    svc.predict_batch([_req(0)])                  # one good cached entry
    boom = RuntimeError("backend down")

    def explode(self, model_cls, reqs):
        raise boom
    monkeypatch.setattr(PredictionService, "_dispatch", explode)
    hit_req = _req(1)                             # served from cache
    fail_req = _req(2, params={"N": 1920})        # needs a dispatch
    svc.submit(hit_req)
    svc.submit(fail_req)
    with pytest.raises(RuntimeError):
        svc.flush()
    assert hit_req.result.get("cached") is True   # hit kept its answer
    assert fail_req.result["status"] == "error"
    assert len(svc.cache) == 1                    # nothing new was cached
    monkeypatch.undo()
    # the failed scenario is a miss (never cached), and recomputes fine
    out = svc.predict_batch([_req(3, params={"N": 1920})])
    assert "cached" not in out[3]


def test_budgeted_and_degraded_requests_are_never_cached():
    svc = PredictionService(cache=True, max_des_ranks=1)
    # rank-guard degrade (timeout_s set, DES over the cap)
    out = svc.predict_batch([_req(0, breakdown=True, timeout_s=60.0)])[0]
    assert out["degraded"] is True
    assert len(svc.cache) == 0
    # plain budgeted request: uncacheable even when it succeeds
    out = svc.predict_batch([_req(1, timeout_s=60.0)])[1]
    assert "cached" not in out and len(svc.cache) == 0
    assert svc.stats["cache_hits"] == 0 == svc.stats["cache_misses"]


def test_isolated_resolution_errors_never_touch_the_cache():
    svc = PredictionService(cache=True)
    out = svc.predict_batch(
        [_req(0), WorkloadRequest(rid=1, workload="hpl",
                                  platform="no-such-machine")],
        isolate_errors=True)
    assert out[1]["status"] == "error"
    assert len(svc.cache) == 1                    # only the good result


# ------------------------------------------------------------- warm pool

def test_warm_pool_first_wave_pays_zero_compiles():
    from repro.core import fastsim
    from repro.workloads import stepsim
    fastsim._compiled.cache_clear()               # cold process state
    stepsim._compiled.cache_clear()
    svc = PredictionService()
    report = svc.warm(["hpl", "transformer"],
                      ["tpu-v5e-pod"], count=4)
    assert report["compiles"] > 0 and report["dispatches"] == 2
    # an identical second warm is fully warm already
    assert svc.warm(["hpl", "transformer"], ["tpu-v5e-pod"],
                    count=4)["compiles"] == 0
    # a real wave with the SAME per-family lane count the warm used
    # (the jit cache is keyed on the padded batch shape)
    pre = fastsim.trace_count() + stepsim.trace_count()
    reqs = [WorkloadRequest(rid=i, workload=w, platform="tpu-v5e-pod")
            for i, w in enumerate(["hpl", "transformer"] * 4)]
    out = svc.predict_batch(reqs)
    assert len(out) == 8
    assert fastsim.trace_count() + stepsim.trace_count() == pre
    snap = svc.metrics.snapshot()["counters"]
    assert snap.get("serve.warm_compiles", 0) == report["compiles"]
    assert snap.get("serve.warm_dispatches") == 4


def test_warm_can_prime_the_result_cache():
    svc = PredictionService(cache=True)
    svc.warm(["hpl"], ["frontera"], count=2, prime_cache=True)
    out = svc.predict_batch([_req(0, params={})])
    assert out[0]["cached"] is True
    assert svc.stats["cache_misses"] == 0


# -------------------------------------------------------------- sharding

def test_shard_single_device_is_bitwise_identical():
    base = PredictionService().predict_batch(
        [_req(i, params={"N": 1536 + 384 * i}) for i in range(3)])
    shard = PredictionService(shard=True).predict_batch(
        [_req(i, params={"N": 1536 + 384 * i}) for i in range(3)])
    assert shard == base                          # exact, not approx


def test_shard_lanes_fallback_is_identity():
    import numpy as np
    from repro.core.fastsim import _shard_lanes
    x = np.arange(8.0)
    trees, sharded = _shard_lanes(False, 8, x)    # sharding off
    assert trees[0] is x and not sharded
    trees, sharded = _shard_lanes(True, 8, x)     # on, but 1 device
    assert trees[0] is x and not sharded


@pytest.mark.parametrize("n, shard, n_dev, padded", [
    (1, False, 4, 1), (3, False, 4, 4), (5, False, 3, 8), (2, True, 4, 4),
    (3, True, 4, 4), (5, True, 4, 8), (1, True, 3, 3), (5, True, 3, 9)])
def test_pad_lanes_rounds_to_the_devices_when_sharded(monkeypatch, n,
                                                      shard, n_dev,
                                                      padded):
    from repro.core import fastsim
    monkeypatch.setattr(fastsim, "shard_device_count", lambda: n_dev)
    lanes = fastsim._pad_lanes(list(range(n)), shard)
    # a power of two, rounded up to a multiple of the devices only when
    # sharded; padding lanes repeat the last live one
    assert lanes == list(range(n)) + [n - 1] * (padded - n)


def test_forced_multi_device_shard_is_bitwise_identical():
    script = textwrap.dedent("""
        import jax
        assert jax.device_count() == 4, jax.device_count()
        from repro.serve import PredictionService, WorkloadRequest
        def reqs():
            return [WorkloadRequest(rid=i, workload="hpl",
                                    platform="frontera",
                                    params={"N": 1536 + 384 * i})
                    for i in range(4)]
        from repro.obs import global_metrics
        base = PredictionService().predict_batch(reqs())
        svc = PredictionService(shard=True)
        with global_metrics(svc.metrics):
            shard = svc.predict_batch(reqs())
        assert shard == base, (shard, base)
        c = svc.metrics.snapshot()["counters"]
        assert c.get("fastsim.sharded_dispatches", 0) >= 1, c

        # each service pads and places its lanes by its own setting: an
        # unsharded service's 2-request wave, dispatched while a sharded
        # service's dispatch is in flight in the same process, pads
        # nothing and shards nothing; the sharded wave pads 2 -> 4 lanes
        from repro.workloads import HPLFastModel
        def two():
            return [WorkloadRequest(rid=i, workload="hpl",
                                    platform="frontera",
                                    params={"N": 1536}) for i in range(2)]
        plain, sharded = PredictionService(), PredictionService(shard=True)
        orig = HPLFastModel.sweep_models.__func__
        inner, nested = {}, []
        def sweep_models(cls, models, *, shard=False):
            if shard and not nested:
                nested.append(True)
                with global_metrics(plain.metrics):
                    inner.update(plain.predict_batch(two()))
            return orig(cls, models, shard=shard)
        HPLFastModel.sweep_models = classmethod(sweep_models)
        try:
            with global_metrics(sharded.metrics):
                outer = sharded.predict_batch(two())
        finally:
            HPLFastModel.sweep_models = classmethod(orig)
        assert inner == outer, (inner, outer)
        cp = plain.metrics.snapshot()["counters"]
        cs = sharded.metrics.snapshot()["counters"]
        assert cp["fastsim.lanes_live"] == 2, cp
        assert cp.get("fastsim.lanes_padded", 0) == 0, cp
        assert "fastsim.sharded_dispatches" not in cp, cp
        assert cs["fastsim.lanes_live"] == 2, cs
        assert cs["fastsim.lanes_padded"] == 2, cs
        assert cs["fastsim.sharded_dispatches"] == 1, cs
        print("OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH="src")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))),
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OK" in proc.stdout


def test_resolution_memo_skips_unhashable_params():
    # list-valued params (e.g. transformer mesh=[4, 8]) build a tuple
    # fine but fail at hash time — the memo must fall back to a fresh
    # resolve, not raise
    from repro.serve import PredictionService, WorkloadRequest
    svc = PredictionService()
    req = WorkloadRequest(rid=0, workload="transformer",
                          platform="tpu-v5e-pod",
                          params={"mesh": [4, 8], "num_layers": 8})
    assert svc._memo_key(req) is None
    out = svc.predict_batch([req])
    assert out[0].get("status") != "error" and "step_s" in out[0]
    assert not svc._resolve_memo
