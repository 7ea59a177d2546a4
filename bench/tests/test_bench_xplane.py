"""The trace reduction on fixed events, and on a small trace recorded on
a TPU v5e (three runs of a tiny float32 loop inside the benchmark's
host spans)."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import xplane  # noqa: E402

RECORDED = Path(__file__).resolve().parent / "data"


def test_union_and_idle_by_host_span():
    # ops (ns): [100, 300) and [300, 400) touch; [600, 700); one op
    # straddles the window's end and is clipped to it
    ops = [("fusion.1", 100, 200), ("fusion.2", 300, 100),
           ("fusion.1", 600, 100), ("copy", 950, 100)]
    mods = [("jit_fn(1)", 100, 300), ("jit_fn(1)", 600, 100),
            ("jit_small(2)", 950, 100)]
    spans = [("bench.wave", 50, 720), ("serve.flush", 400, 600),
             ("bench.generate", 720, 900)]
    r = xplane.reduce_events([{"XLA Ops": ops, "XLA Modules": mods}],
                             spans, 0, 1000)
    assert r["window_s"] == pytest.approx(1e-6)
    # busy: [100, 400) + [600, 700) + [950, 1000)
    assert r["busy_s"] == pytest.approx(450e-9)
    idle = dict(r["breakdown"]["idle_gaps"])
    # each gap goes to the innermost span around its middle: [0, 100)
    # to the wave, [400, 600) to flush inside it, [700, 950) to generate
    assert idle == pytest.approx({"bench.wave": 100e-9,
                                  "serve.flush": 200e-9,
                                  "bench.generate": 250e-9})
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(r["window_s"])
    ops_s = dict(r["breakdown"]["device_ops"])
    assert ops_s == pytest.approx({"fusion.1": 300e-9, "fusion.2": 100e-9,
                                   "copy": 50e-9})
    assert list(ops_s) == ["fusion.1", "fusion.2", "copy"]
    assert r["modules"]["jit_fn(1)"] == (2, pytest.approx(400e-9))


def test_nested_ops_keep_their_own_time():
    """A loop op holds its body's ops: each keeps only its own time, and
    HLO text is cut to the instruction's name."""
    ops = [("%while.5 = (f32[8]) while(%t), body=%b", 0, 1000),
           ("%fusion.1 = f32[8] fusion(%p)", 100, 300),
           ("%fusion.2 = f32[8] fusion(%q)", 500, 200),
           ("%fusion.1 = f32[8] fusion(%p)", 800, 300)]   # past the loop
    r = xplane.reduce_events([{"XLA Ops": ops}], [], 0, 2000)
    assert r["busy_s"] == pytest.approx(1100e-9)
    assert dict(r["breakdown"]["device_ops"]) == pytest.approx(
        {"while.5": 300e-9, "fusion.1": 600e-9, "fusion.2": 200e-9})


def test_two_devices_average_and_no_device():
    one = {"XLA Ops": [("a", 0, 500)]}
    two = {"XLA Ops": [("a", 0, 250)]}
    r = xplane.reduce_events([one, two, {}], [], 0, 1000)
    assert r["busy_s"] == pytest.approx(375e-9)
    assert xplane.reduce_events([{}], [], 0, 1000) is None


def test_recorded_tpu_trace(tmp_path):
    import shutil
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    shutil.copy(RECORDED / "tiny_tpu.xplane.pb", d / "host.xplane.pb")
    r = xplane.reduce_trace(tmp_path, "bench.traced")
    assert 0 < r["busy_s"] < r["window_s"]
    (name, (count, secs)), = [(k, v) for k, v in r["modules"].items()
                              if "lambda" in k or "jit" in k][:1]
    assert count == 3 and secs == pytest.approx(r["busy_s"], rel=0.05)
    assert 1 <= len(r["breakdown"]["device_ops"]) <= 10
    idle = dict(r["breakdown"]["idle_gaps"])
    assert "bench.generate" in idle
    assert sum(idle.values()) + r["busy_s"] == pytest.approx(r["window_s"],
                                                             rel=1e-6)
