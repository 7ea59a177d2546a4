"""The node-aware cells on the CPU: ``reference_nodes`` against the program
and against ``reference``, the Summit configuration against its TOP500
row, the float32 control against Summit's limit, and the Summit cell end
to end at a tiny size."""
import csv
import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import reference  # noqa: E402
import reference_nodes  # noqa: E402
from harness import load_json  # noqa: E402

CONFIGS = BENCH / "configs"
ROW = (BENCH.parent / "src" / "repro" / "top500" / "data"
       / "top500_sample_2020_06.csv")


def summit():
    return load_json(CONFIGS / "summit.json")


def small(plat, P, Q, N, nb, **over):
    plat = json.loads(json.dumps(plat))
    plat["scale"].update(grid=[P, Q], hpl_n=N, hpl_nb=nb)
    for section, fields in over.items():
        plat[section].update(fields)
    return plat


def scaled_set(base, rng, n=3):
    return [reference.scaled(base, {
        k: float(np.exp(rng.uniform(np.log(0.5), np.log(2.0))))
        for k in reference.SCALE_FIELDS}) for _ in range(n)]


@pytest.mark.parametrize("P,Q,N,nb,R,pmap", [
    (4, 12, 3000, 128, 6, "row"), (6, 3, 2500, 96, 6, "row"),
    (4, 6, 2437, 96, 2, "row"), (8, 4, 3001, 128, 4, "col"),
    (4, 8, 2000, 64, 4, "col"), (2, 9, 2600, 128, 6, "col"),
    (3, 4, 2000, 128, 6, "col"), (2, 8, 2600, 128, 6, "col"),
    (3, 2, 2000, 128, 4, "row")])
def test_reference_nodes_matches_program(P, Q, N, nb, R, pmap):
    """Both mappings, with R < P, R = P, P | R and the mirrors for "row",
    a last node that holds part of a block among them.  The reference
    sums each hop along the ring where the program multiplies counts of
    hops, so they may part in the last bits."""
    from repro.core.apps.hpl import HPLConfig
    from repro.core.fastsim import FastSimParams, sweep_hpl
    rng = np.random.default_rng(P * 100 + Q + R)
    base = summit()["machines"][0]
    plats = [small(p, P, Q, N, nb, scale={"ranks_per_node": R},
                   mpi={"pmap": pmap})
             for p in scaled_set(base, rng, 4)]
    ref = reference_nodes.published_times(plats)
    prms = [FastSimParams(**reference_nodes.rank_params(p),
                          ranks_per_node=R) for p in plats]
    cfg = HPLConfig(N=N, nb=nb, P=P, Q=Q, pmap=pmap)
    got = [r["time_s"] for r in sweep_hpl(cfg, prms)]
    np.testing.assert_allclose(got, ref, rtol=1e-13, atol=0)
    blind = reference.published_times(plats)
    assert np.all(np.abs(blind - ref) / ref > 1e-6)


@pytest.mark.parametrize("config,sizes", [
    ("frontera.json", (4, 5, 4000, 128)), ("frontera.json", (7, 4, 3001, 96)),
    ("summit.json", (4, 6, 3000, 128))])
def test_reference_nodes_is_reference_at_one_rank_per_node(config, sizes):
    base = load_json(CONFIGS / config)["machines"][0]
    plats = [small(p, *sizes, scale={"ranks_per_node": 1})
             for p in scaled_set(base, np.random.default_rng(3))]
    for dtype in (np.float64, np.float32):
        a = reference_nodes.published_times(plats, dtype)
        b = reference.published_times(plats, dtype)
        assert a.tobytes() == b.tobytes()


@pytest.mark.parametrize("P,Q,R,pmap", [(6, 4, 4, "col"), (4, 6, 4, "row"),
                                        (3, 5, 2, "col")])
def test_reference_nodes_refuses_nodes_that_hold_no_block(P, Q, R, pmap):
    plat = small(summit()["machines"][0], P, Q, 2000, 128,
                 scale={"ranks_per_node": R}, mpi={"pmap": pmap})
    with pytest.raises(ValueError, match="block"):
        reference_nodes.published_times([plat])


def test_summit_config_is_its_top500_row():
    """The record loads as a Platform and gives back the same record; its
    published figures and the node count, peak and fabric it assumes
    follow from the vendored list row by the fleet's inference rules."""
    from repro.platforms import Platform
    cfg = summit()
    rec = cfg["machines"][0]
    plat = Platform.from_dict(rec)
    assert plat.to_dict() == rec
    assert (plat.scale.ranks_per_node, plat.mpi.pmap) == (6, "row")
    with open(ROW, newline="") as fh:
        row = next(r for r in csv.DictReader(fh) if r["System"] == "Summit")

    def num(key):
        return float(row[key].replace(",", ""))
    pub = cfg["published"]
    assert (pub["N"], pub["rmax_tflops"], pub["rpeak_tflops"]) == (
        num("Nmax"), num("Rmax [TFlop/s]"), num("Rpeak [TFlop/s]"))
    rules = load_json(CONFIGS / "top500-2020-06.json")["inference"]
    fam = next(f for f in rules["cpu_families"] if f["name"] == "power9")
    cores = fam["sockets_per_node"] * 22
    nodes = (num("Total Cores") - num("Accelerator/Co-Processor Cores")) \
        // cores
    assert nodes == pub["nodes"] == plat.scale.n_nodes
    assert plat.scale.n_nodes * 6 == 144 * 192 == plat.hpl_config().n_ranks
    assert plat.scale.hpl_n == pub["N"] == 42_900 * plat.scale.hpl_nb
    assert plat.scale.reported_tflops == pub["rmax_tflops"]
    gpus = num("Rpeak [TFlop/s]") * 1e12 / nodes \
        - cores * fam["flops_per_cycle"] * 3.07e9
    assert plat.node.accel_peak_flops == pytest.approx(gpus, rel=1e-12)
    assert plat.node.peak_flops == pytest.approx(
        rules["accel_sustained_frac"] * gpus, rel=1e-12)
    assert plat.node.gemm_efficiency == rules["gemm_efficiency"]
    assert plat.node.blas_latency == rules["accel_blas_latency"]
    assert "EDR" in row["Interconnect"] and "Dual-rail" in row["Interconnect"]
    assert plat.fabric.link_bw == 2 * 12.5e9
    assert cfg["reduced"] == [] and cfg["check"]["max_rel_gap"] == 1e-6


def test_float32_control_fails_summits_limit():
    """The reference one precision down reads above Summit's limit on each
    of three scaled machines, at a size a test can hold (2,084 panels; at
    a few hundred panels float32's error is of the limit's own order)."""
    cfg = summit()
    plats = scaled_set(small(cfg["machines"][0], 8, 12, 200000, 96),
                       np.random.default_rng(0))
    low = reference_nodes.published_times(plats, np.float32)
    ref = reference_nodes.published_times(plats)
    gaps = np.abs(low - ref) / ref
    assert np.all(gaps > 3 * cfg["check"]["max_rel_gap"]), gaps


def make_root(tmp_path: Path) -> Path:
    """A checkout holding the benchmark with Summit cut to a 4 x 12 grid
    of 32 panels (6 ranks a node, row-major) and Frontera to 4 x 5."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root / "BENCHMARK.json")
    for name, (P, Q, N, nb) in (("summit", (4, 12, 4000, 128)),
                                ("frontera", (4, 5, 4000, 128))):
        path = root / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg["machines"][0]["scale"].update(grid=[P, Q], hpl_n=N, hpl_nb=nb)
        path.write_text(json.dumps(cfg))
    return root


def _line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_summit_cell_end_to_end(tmp_path, capsys):
    root = make_root(tmp_path)
    spec = harness.load_json(root / "BENCHMARK.json")
    cell = "summit-whatif"
    for traced in (False, True):
        rc = harness.run_cell(cell, 2**31 + 9, 0.5, traced, root=root,
                              chip=False, persistent_cache=False, workers=1)
        out, err = capsys.readouterr()
        assert rc == 0, err
        line = _line(out)
        assert line["correct"] is True and line["failed"] == 0
        assert line["attempted"] % 8 == 0
        want = {m["name"] for m in harness.cell_metrics(spec, cell, traced)
                if not traced or m["source"] != "device_trace"}
        assert set(line["metrics"]) == want
    metrics = line["metrics"]
    assert metrics["node_lane_share"]["value"] == 1.0
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["grid_occupancy"]["value"] == 1.0    # (32, 4, 12)


def test_summit_control_reads_above_the_limit(tmp_path, capsys):
    from control import float32_in_place
    root = make_root(tmp_path)
    path = root / "bench" / "configs" / "summit.json"
    cfg = json.loads(path.read_text())
    cfg["machines"][0]["scale"].update(grid=[8, 12], hpl_n=40000, hpl_nb=96)
    path.write_text(json.dumps(cfg))
    rc = harness.run_cell("summit-whatif", 17, 0.0, False, root=root,
                          chip=False, persistent_cache=False, workers=1,
                          place=float32_in_place())
    out, err = capsys.readouterr()
    assert rc == 0, err
    line = _line(out)
    assert line["correct"] is False
    gap = line["check"]["max_rel_gap"]
    assert gap["value"] > gap["limit"]

