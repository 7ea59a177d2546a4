"""The benchmark's plain reference against the program, at small sizes on
the CPU, and the metric arithmetic on fixed inputs."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
from harness import load_json, load_module  # noqa: E402

CONFIGS = BENCH / "configs"


def frontera():
    return load_json(CONFIGS / "frontera.json")


def fleet_config():
    return load_json(CONFIGS / "top500-2020-06.json")


def fleet_machines(cfg=None):
    """The reference's records of the list's machines, by its rules."""
    cfg = cfg or fleet_config()
    return [reference.infer(r, cfg["inference"]) for r in cfg["rows"]]


def program_platforms(cfg=None):
    """The program's inference over the same rows."""
    from repro.top500 import Top500Row, infer_platforms
    cfg = cfg or fleet_config()
    return infer_platforms(Top500Row(**r) for r in cfg["rows"])


def small(plat, P, Q, N, nb):
    plat = json.loads(json.dumps(plat))
    plat["scale"].update(grid=[P, Q], hpl_n=N, hpl_nb=nb)
    return plat


@pytest.mark.parametrize("P,Q,N,nb", [(2, 2, 1000, 64), (3, 5, 2437, 96),
                                      (7, 4, 4000, 128), (6, 9, 3001, 64)])
def test_recurrence_matches_program(P, Q, N, nb):
    from repro.core.apps.hpl import HPLConfig
    from repro.core.fastsim import FastSimParams, sweep_hpl
    rng = np.random.default_rng(P * 100 + Q)
    base = frontera()["machines"][0]
    plats = [small(reference.scaled(base, {
        k: float(np.exp(rng.uniform(np.log(0.5), np.log(2.0))))
        for k in reference.SCALE_FIELDS}), P, Q, N, nb) for _ in range(4)]
    ref = reference.published_times(plats)
    prms = [FastSimParams(**reference.rank_params(p)) for p in plats]
    got = [r["time_s"] for r in sweep_hpl(HPLConfig(N=N, nb=nb, P=P, Q=Q),
                                          prms)]
    np.testing.assert_allclose(got, ref, rtol=1e-14, atol=0)


def test_rank_params_match_platform_adapter():
    from repro.platforms import Platform
    pairs = [(Platform.from_dict(frontera()["machines"][0]),
              frontera()["machines"][0])]
    pairs += list(zip(program_platforms(), fleet_machines()))
    for plat, record in pairs:
        want = plat.fastsim()
        got = reference.rank_params(record)
        assert {k: getattr(want, k) for k in got} == got


@pytest.mark.parametrize("edition", ["2020_06", "2020_11"])
def test_inference_matches_program(edition):
    """The reference's reading of the configuration's rules gives every
    field the program's TOP500 inference gives, on both vendored lists."""
    import dataclasses
    from repro.top500 import infer_platform, load_sample
    rules = fleet_config()["inference"]

    def fields(mine, theirs):
        for k, v in mine.items():
            if isinstance(v, dict):
                yield from fields(v, theirs[k])
            else:
                yield k, v, theirs[k]

    for row in load_sample(edition=edition):
        record = dataclasses.asdict(row)
        record.pop("schema_version")
        mine = reference.infer(record, rules)
        prog = infer_platform(row)
        assert mine["provenance"] == [["fabric_group",
                                       prog.provenance_dict["fabric_group"]]]
        mine = {k: v for k, v in mine.items()
                if k not in ("name", "provenance")}
        for k, a, b in fields(mine, prog.to_dict()):
            assert a == b, (row.rank, k)


def test_fleet_config_is_the_vendored_list():
    import dataclasses
    from repro.top500 import load_sample
    cfg = fleet_config()
    want = [dataclasses.asdict(r) for r in load_sample(edition="2020_06")]
    for r in want:
        r.pop("schema_version")
    assert cfg["rows"] == want
    assert cfg["reduced"] == ["rows"] and len(cfg["rows"]) == 51


def test_frontera_config_is_the_registry_entry():
    from repro.platforms import get_platform
    cfg = frontera()
    assert cfg["machines"][0] == get_platform("frontera").to_dict()
    sc = cfg["machines"][0]["scale"]
    pub = cfg["published"]
    assert (sc["hpl_n"], sc["hpl_nb"], list(sc["grid"]), sc["n_nodes"],
            sc["reported_tflops"]) == (pub["N"], pub["nb"],
                                       [pub["P"], pub["Q"]], pub["nodes"],
                                       pub["rmax_tflops"])


def test_fleet_matches_program_at_small_tuning():
    from generator import apply_scales
    from repro.top500 import predict_fleet
    from repro.top500.fleet import FleetTuning
    cfg = fleet_config()
    tuning = dict(cfg["tuning"], max_ranks=16, panels_cap=48)
    rng = np.random.default_rng(5)
    scales = [{"link_bw": float(rng.uniform(0.5, 2)),
               "gemm_eff": float(rng.uniform(0.5, 2))}
              for _ in cfg["rows"]]
    plats = [reference.scaled(p, s) for p, s in zip(fleet_machines(),
                                                    scales)]
    rep = predict_fleet([apply_scales(p, s) for p, s in
                         zip(program_platforms(), scales)],
                        tuning=FleetTuning(**tuning), calibrate=True)
    ref = reference.fleet(plats, tuning)
    assert [(e.cfg.N, e.cfg.nb, e.cfg.P, e.cfg.Q) for e in rep.entries] == \
        [reference.tune(p, tuning)[:4] for p in plats]
    np.testing.assert_allclose([e.predicted_tflops for e in rep.entries],
                               ref["predicted_tflops"], rtol=1e-14)
    np.testing.assert_allclose([e.calibrated_tflops for e in rep.entries],
                               ref["calibrated_tflops"], rtol=1e-14)
    assert rep.calibration.heldout_median_abs_err == pytest.approx(
        ref["heldout_median_abs_err"], rel=1e-14)


@pytest.mark.parametrize("config,sizes", [
    ("frontera.json", (8, 9, 40000, 96)), ("top500-2020-06.json", None)])
def test_float32_control_fails_the_limit(config, sizes):
    """The control (the reference one precision down) reads above the
    limit even at a size a test can hold: the limit separates them."""
    cfg = load_json(CONFIGS / config)
    limit = cfg["check"]["max_rel_gap"]
    if sizes:
        plats = [small(cfg["machines"][0], *sizes)]
        low = reference.published_times(plats, np.float32)
        ref = reference.published_times(plats)
        gap = float(np.max(np.abs(low - ref) / ref))
    else:
        tuning = dict(cfg["tuning"], max_ranks=16, panels_cap=48)
        low = reference.fleet(fleet_machines(cfg), tuning, np.float32)
        ref = reference.fleet(fleet_machines(cfg), tuning)
        gap = max(float(np.max(np.abs(low[k] - ref[k]) / ref[k]))
                  for k in ("predicted_tflops", "calibrated_tflops"))
    assert gap > 3 * limit


def test_grid_occupancy_of_the_configs():
    """Live panel steps x ranks over the dispatched bucket: Frontera's
    8 lanes in (24576, 96, 96); the fleet's 51 proxies in 64 lanes of
    (4096, 32, 32)."""
    occ = load_module(BENCH / "metrics" / "grid_occupancy.py")
    fr = frontera()["machines"][0]["scale"]
    fr_work = -(-fr["hpl_n"] // fr["hpl_nb"]) * fr["grid"][0] * fr["grid"][1]
    cfg = fleet_config()
    fl_work = 0
    for p in fleet_machines(cfg):
        N, nb, P, Q, _ = reference.tune(p, cfg["tuning"])
        fl_work += -(-N // nb) * P * Q

    def run(lanes, padded, bucket, work):
        entry = SimpleNamespace(live_work=lambda wave, answers: work)
        stats = {"fastsim.lanes_live": lanes, "fastsim.lanes_padded": padded,
                 f'fastsim.compile_hits{{bucket="{bucket}"}}': 1}
        return SimpleNamespace(window_stats=stats, entry=entry, waves=[
            SimpleNamespace(wave=None, answers=0)])

    assert occ.read(run(8, 0, "24576x96x96", 8 * fr_work)) == pytest.approx(
        0.8547, abs=1e-4)
    assert occ.read(run(51, 13, "4096x32x32", fl_work)) == pytest.approx(
        0.713, abs=1e-3)
    two = run(8, 0, "24576x96x96", 1)
    two.window_stats['fastsim.compile_hits{bucket="4096x32x32"}'] = 1
    assert occ.read(two) is None
