"""CPU rehearsal of the benchmark harness at tiny sizes: the chip check,
the seeded traffic, both cells end to end, cells and metrics found by
name, and faults in the timed path that the check must catch."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
from generator import Traffic  # noqa: E402

CELLS = ("frontera-whatif", "top500-fleet")


def make_root(tmp_path: Path, frontera=(4, 5, 4000, 128)) -> Path:
    """A checkout holding the benchmark with its configurations cut to a
    size the CPU runs in a second: Frontera on a 4 x 5 grid with 32
    panels; six machines of the list on proxies of at most 16 ranks."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    configs = root / "bench" / "configs"
    fr = json.loads((configs / "frontera.json").read_text())
    P, Q, N, nb = frontera
    fr["machines"][0]["scale"].update(grid=[P, Q], hpl_n=N, hpl_nb=nb)
    (configs / "frontera.json").write_text(json.dumps(fr))
    fl = json.loads((configs / "top500-2020-06.json").read_text())
    fl["rows"] = fl["rows"][:6]
    fl["tuning"].update(max_ranks=16, panels_cap=48)
    (configs / "top500-2020-06.json").write_text(json.dumps(fl))
    return root


def run(root, cell, traced=False, seconds=0.5, seed=2**31 + 7, capsys=None):
    rc = harness.run_cell(cell, seed, seconds, traced, root=root, chip=False,
                          persistent_cache=False, workers=1)
    out, err = capsys.readouterr()
    lines = out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err


def test_run_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=BENCH.parent, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs an accelerator" in proc.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_run_cell_refuses_without_a_chip(cell, capsys):
    rc = harness.run_cell(cell, 1, 1.0, False, chip=True,
                          persistent_cache=False)
    out, err = capsys.readouterr()
    assert rc == 3 and out == "" and "needs an accelerator" in err


@pytest.mark.parametrize("traffic,n", [("whatif-8", 1), ("whatif-8", 3),
                                       ("fleet-all", 51)])
def test_same_seed_same_requests(traffic, n):
    spec = harness.load_json(BENCH / "traffic" / f"{traffic}.json")
    waves = [[Traffic(spec, n, seed).next_wave() for _ in range(3)]
             for seed in (2**31 + 11, 2**31 + 11, 5)]
    assert waves[0] == waves[1]
    assert waves[0] != waves[2]
    # the seed draws the scales and the order, never the work
    for a, b in zip(waves[0], waves[2]):
        assert sorted(i for i, _ in a) == sorted(i for i, _ in b)
        assert all(set(s) == set(spec["scales"]) for _, s in a)
        assert all(0.5 <= v <= 2.0 for _, s in a for v in s.values())


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_end_to_end(cell, tmp_path, capsys):
    root = make_root(tmp_path)
    spec = harness.load_json(root / "BENCHMARK.json")
    rc, line, err = run(root, cell, capsys=capsys)
    assert rc == 0, err
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    want = {m["name"] for m in harness.cell_metrics(spec, cell, False)}
    assert set(line["metrics"]) == want
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert list(line)[-1] == "check"
    assert line["device"]["platform"] == "cpu"
    assert err.strip().splitlines()[-1].startswith("check failed 0 limit 0")

    rc, line, err = run(root, cell, traced=True, capsys=capsys)
    assert rc == 0, err
    assert line["correct"] is True
    got = set(line["metrics"])
    host_side = {m["name"] for m in harness.cell_metrics(spec, cell, True)
                 if m["source"] != "device_trace"}
    assert got == host_side     # the CPU backend has no device plane
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < line["metrics"]["grid_occupancy"]["value"] <= 1


def test_new_cell_and_metric_found_by_name(tmp_path, capsys):
    """A configuration, a traffic mix and a per-layer metric added as new
    files and new BENCHMARK.json entries, with no file edited."""
    root = make_root(tmp_path)
    bench = root / "bench"
    fr = json.loads((bench / "configs" / "frontera.json").read_text())
    fr["name"] = "dummy"
    (bench / "configs" / "dummy.json").write_text(json.dumps(fr))
    whatif = json.loads((bench / "traffic" / "whatif-8.json").read_text())
    (bench / "traffic" / "dummy-3.json").write_text(json.dumps(
        dict(whatif, machines=3, scales={"link_bw": [0.9, 1.1]})))
    (bench / "metrics" / "dummy_waves.py").write_text(
        "def read(run):\n    return float(len(run.waves))\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "dummy", "source": "https://example.org",
                            "file": "bench/configs/dummy.json",
                            "reduced": [], "why": "a test"})
    spec["workloads"].append({"name": "dummy.three", "config": "dummy",
                              "traffic": "dummy-3", "chips": 1,
                              "why": "a test"})
    spec["per_layer"].append({"name": "dummy_waves", "unit": "count",
                              "better": "higher", "source": "host_clock",
                              "layer": "serve front end",
                              "moves": "predictions_per_s",
                              "workloads": ["dummy.three"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    rc, line, err = run(root, "dummy.three", traced=True, capsys=capsys)
    assert rc == 0, err
    assert line["correct"] is True and line["attempted"] % 3 == 0
    assert line["metrics"]["dummy_waves"]["value"] >= 1
    rc, line, err = run(root, "frontera-whatif", traced=True, capsys=capsys)
    assert "dummy_waves" not in line["metrics"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_reads_above_the_limit(cell, tmp_path, capsys):
    """The float32 reference in the program's place, run through the
    harness, reads correct: false (at 417 panels: rounding in float32
    grows with the panel count)."""
    from control import float32_in_place
    root = make_root(tmp_path, frontera=(8, 9, 40000, 96))
    for seed in (2**31 + 3, 17):
        rc = harness.run_cell(cell, seed, 0.0, False, root=root, chip=False,
                              persistent_cache=False, workers=1,
                              place=float32_in_place())
        out, err = capsys.readouterr()
        line = json.loads(out.strip().splitlines()[-1])
        assert rc == 0, err
        assert line["correct"] is False
        gap = line["check"]["max_rel_gap"]
        assert gap["value"] > gap["limit"]
        assert line["check"]["samples"]["value"] == \
            line["check"]["samples"]["limit"]


def test_check_compares_every_lane():
    """The sample holds one answer of each lane position, so a fault in
    any one lane is compared in every run."""
    from types import SimpleNamespace
    spec = harness.load_json(BENCH / "traffic" / "whatif-8.json")
    entry = SimpleNamespace(samples=lambda wave, answers: list(
        zip(wave, answers)))
    waves = [harness.WaveRecord([(w, p) for p in range(8)],
                                [(w, p) for p in range(8)], 0.0, 1.0, 0, {})
             for w in range(13)]
    run_ = harness.Run(cell={}, config={}, traffic=spec, entry=entry,
                       waves=waves)
    for seed in (1, 2**31 + 5):
        chosen = harness.sample(run_, seed)
        assert sorted(p for (_, p), _ in chosen) == list(range(8))
    assert harness.sample(run_, 1) == harness.sample(run_, 1)


def test_clients_share_the_window():
    """Two clients keep a wave each in flight; every wave sent is
    answered and kept in the traffic's order, and the window closes
    after the last answer."""
    import threading
    import time
    from types import SimpleNamespace

    def serve(built):
        time.sleep(0.02)
        return threading.get_ident()
    spec = dict(harness.load_json(BENCH / "traffic" / "fleet-all.json"),
                clients=2)
    run_ = harness.Run(cell={}, config={}, traffic=spec,
                       entry=SimpleNamespace(build=lambda w: w, serve=serve))
    harness._window(run_, Traffic(spec, 3, 2**31 + 3), 0.2, False, None,
                    None)
    waves = run_.waves
    assert [w.index for w in waves] == list(range(len(waves)))
    assert len({w.answers for w in waves}) == 2
    assert any(a.t0 < b.t1 and b.t0 < a.t1
               for a, b in zip(waves, waves[1:]))
    assert run_.window_s >= 0.2
    again = Traffic(spec, 3, 2**31 + 3)
    assert [w.wave for w in waves] == [again.next_wave() for _ in waves]


def _stale_step(monkeypatch, fastsim):
    """Every panel step after the first returns its state unchanged."""
    orig = fastsim._sim_core

    def core(N, nb, P, Q, prm, n_panels_max, P_max, Q_max):
        return orig(N, nb, P, Q, prm, 1, P_max, Q_max)
    monkeypatch.setattr(fastsim, "_sim_core", core)


def _half_batch(monkeypatch, fastsim):
    """Half of each sweep left out, answered by the mean of the rest."""
    orig = fastsim.sweep_hpl

    def sweep(configs, params, **kw):
        out = orig(configs, params, **kw)
        keep = max(len(out) // 2, 1)
        mean = sum(r["time_s"] for r in out[:keep]) / keep
        cfgs = configs if isinstance(configs, (list, tuple)) else \
            [configs] * len(out)
        return out[:keep] + [fastsim._result(c, mean) for c in cfgs[keep:]]
    monkeypatch.setattr(fastsim, "sweep_hpl", sweep)


def _altered_answer(monkeypatch, fastsim):
    """The first answer of each sweep altered by one part in 10,000."""
    orig = fastsim.sweep_hpl

    def sweep(configs, params, **kw):
        out = orig(configs, params, **kw)
        cfg0 = configs[0] if isinstance(configs, (list, tuple)) else configs
        out[0] = fastsim._result(cfg0, out[0]["time_s"] * (1 + 1e-4))
        return out
    monkeypatch.setattr(fastsim, "sweep_hpl", sweep)


@pytest.mark.parametrize("fault", [_stale_step, _half_batch, _altered_answer],
                         ids=lambda f: f.__name__.strip("_"))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_in_the_timed_path_is_not_correct(cell, fault, tmp_path,
                                                monkeypatch, capsys):
    """A run with the program broken underneath reads correct: false.
    (No cell spans chips, so there is no exchange between chips to
    leave out.)"""
    from repro.core import fastsim
    root = make_root(tmp_path)
    fastsim._compiled.cache_clear()
    fault(monkeypatch, fastsim)
    try:
        rc, line, err = run(root, cell, seconds=0.0, capsys=capsys)
    finally:
        monkeypatch.undo()
        fastsim._compiled.cache_clear()
    assert rc == 0, err
    assert line["correct"] is False
    gap = line["check"]["max_rel_gap"]
    assert gap["value"] > gap["limit"]
