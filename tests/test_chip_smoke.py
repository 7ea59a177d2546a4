"""chip_smoke.py rehearsed on the CPU at a tiny size: its phases and
checks, the four-device sharded phase on forced host devices, the
refusal to run without a TPU, and the compile-cache placement rules."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def tiny():
    """A smoke run at a size the CPU takes in seconds."""
    from repro.top500 import FleetTuning
    return chip_smoke.Sizes(
        frontera={"N": 3072, "nb": 128, "P": 4, "Q": 4},
        pupmaya={"N": 2048, "nb": 128, "P": 2, "Q": 3},
        fleet_rows=8,
        fleet_kw={"tuning": FleetTuning(max_ranks=16, panels_cap=32)})


def test_one_chip_phases_pass_at_tiny_size():
    lines = []
    failures = chip_smoke.smoke_one_chip(tiny(), log=lines.append)
    assert failures == [], failures
    text = "\n".join(lines)
    for name in chip_smoke.phases(tiny()):
        assert f"{name}: cold" in text
    assert "repeated waves" in text and "new traces 0" in text
    assert "verify anchor: 0.05853829" in text


def test_whatif_wave_shares_one_geometry_and_orders_hardware():
    reqs = chip_smoke.whatif_requests(tiny())
    assert len(reqs) == len(chip_smoke.LINK_SCALES) * len(
        chip_smoke.GEMM_SCALES)
    times = chip_smoke.serve(reqs)
    assert len(set(times)) == len(times)       # every what-if took effect
    g = len(chip_smoke.GEMM_SCALES)
    assert times[-g] < times[0]                # 4x the bandwidth is faster


def test_four_chip_phase_on_forced_host_devices():
    script = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT / "tests")!r})
        import jax
        assert jax.device_count() == 4, jax.device_count()
        import test_chip_smoke as t
        print('FAILURES', t.chip_smoke.smoke_four_chips(t.tiny()))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          cwd=str(ROOT), capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "FAILURES []" in proc.stdout, proc.stdout
    assert "padded to 8 lanes" in proc.stdout
    assert "shards 4" in proc.stdout


def test_main_refuses_without_a_tpu():
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)


def test_main_prints_the_result_line_last(monkeypatch, capsys):
    # the platform check is bypassed here, and only here, so the whole
    # script runs on the CPU at a tiny size
    from repro import compile_cache
    monkeypatch.setattr(chip_smoke, "require_tpu",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(chip_smoke, "FULL", tiny())
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "(not set in tests)")
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    dev = jax.devices()[0]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": 1}}


def test_compile_cache_respects_the_environment(monkeypatch):
    from repro import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert calls == []                     # JAX's own reading stands
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path == str(ROOT / ".jax_cache")
    assert calls == [("jax_compilation_cache_dir", path)]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
