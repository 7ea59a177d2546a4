"""The readers of the program's dispatch spans on a fake run: a value
from the window's histograms, None where the program has no such span
(the parent of the change that added them), and for ``wait_excess_ms``
None without a device trace."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

READERS = ("launch_ms", "sweep_prep_ms", "wait_excess_ms")
SPANS = {"launch_ms": "fastsim.launch_s", "sweep_prep_ms":
         "fastsim.prepare_s", "wait_excess_ms": "fastsim.wait_s"}
#: two executions of the recurrence, 2.32 s of device time each
TRACE = {"busy_s": 4.64, "window_s": 4.7,
         "modules": {"jit_hpl_recurrence_params": (2, 4.64),
                     "jit_convert_element_type": (2, 1e-6)}}


def reader(name):
    return harness.load_module(BENCH / "metrics" / f"{name}.py")


def fake_run(stats, trace=TRACE):
    return harness.Run(cell={}, config={}, traffic={}, entry=None,
                       window_stats=stats, trace=trace)


def test_launch_and_prepare_are_means_in_ms():
    stats = {"fastsim.launch_s.sum": 0.0051, "fastsim.launch_s.count": 3,
             "fastsim.prepare_s.sum": 0.0006, "fastsim.prepare_s.count": 4}
    assert reader("launch_ms").read(fake_run(stats)) == pytest.approx(1.7)
    assert reader("sweep_prep_ms").read(fake_run(stats)) == \
        pytest.approx(0.15)


def test_wait_excess_is_the_wait_beyond_the_execution():
    stats = {"fastsim.wait_s.sum": 3 * 2.326, "fastsim.wait_s.count": 3}
    got = reader("wait_excess_ms").read(fake_run(stats))
    assert got == pytest.approx(6.0)          # 2.326 s less 2.32 s


@pytest.mark.parametrize("name", READERS)
def test_no_span_reads_nothing(name):
    """A program without the span (or a window with no dispatch) gives
    None, so the line leaves the metric out."""
    assert reader(name).read(fake_run({})) is None
    assert reader(name).read(fake_run({SPANS[name] + ".count": 0})) is None


def test_wait_excess_needs_the_device_trace():
    stats = {"fastsim.wait_s.sum": 2.33, "fastsim.wait_s.count": 1}
    assert reader("wait_excess_ms").read(fake_run(stats, trace=None)) is None
    assert reader("wait_excess_ms").read(
        fake_run(stats, trace=dict(TRACE, modules={}))) is None
