"""Compile the chip's programs for a described TPU v5e, without a chip.

The TPU compiler refuses what interpret mode and the CPU backend accept
(block shapes off the (8, 128) tiling, mask relayouts), so the three
Pallas kernels are compiled here at the widths of the models they serve,
together with the stepsim core and the fastsim ``params`` core at its
smallest bucket, one block of panels, and at a bucket of two blocks,
where the serial panel loop nests inside the loop over blocks (the
compile time does not depend on the bucket otherwise).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and it keeps it until it exits, so
every compile runs in the test's own process.  The persistent
compilation cache is off around each compile: a program compiled for a
described chip is written to it but cannot be read back without one.
"""
import os

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


@pytest.fixture
def spec(one_chip, no_persistent_cache):
    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def test_flash_attention_compiles_at_qwen2_widths(spec):
    # qwen2-0.5b: 2 kv groups x 7 query heads, head_dim 64, 4k context
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    q = spec((1, 4096, 2, 7, 64), jnp.bfloat16)
    kv = spec((1, 4096, 2, 64), jnp.bfloat16)
    c = _compile(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True),
                 q, kv, kv)
    assert "tpu_custom_call" in c.as_text()


def test_ssd_scan_compiles_at_mamba2_widths(spec):
    # mamba2-780m: 48 heads, head_dim 64, state 128, chunk 256
    from repro.kernels.ssd_scan.kernel import ssd_scan
    c = _compile(lambda *a: ssd_scan(*a, chunk=256),
                 spec((1, 4096, 48, 64), jnp.bfloat16),
                 spec((1, 4096, 48), jnp.float32),
                 spec((48,), jnp.float32),
                 spec((1, 4096, 48, 128), jnp.bfloat16),
                 spec((1, 4096, 48, 128), jnp.bfloat16))
    assert "tpu_custom_call" in c.as_text()


def test_masked_min_rows_compiles_with_256_tiles(spec):
    from repro.kernels.maxmin_fair.kernel import masked_min_rows
    c = _compile(lambda a, v: masked_min_rows(a, v, bf=256, bl=256),
                 spec((4096, 1024), jnp.int8), spec((1024,), jnp.float32))
    assert "tpu_custom_call" in c.as_text()


def test_stepsim_core_compiles_in_float64(spec):
    from repro.workloads import stepsim
    with jax.enable_x64(True):
        p = stepsim.StepParams(**{n: spec((8,), jnp.float64)
                                  for n in stepsim._STEP_FIELDS})
        c = stepsim._compiled().lower(p).compile()
    assert c.memory_analysis() is not None


def _fastsim_params_core(spec, n_panels_max):
    from repro.core import fastsim
    with jax.enable_x64(True):
        prm = fastsim.FastSimParams(**{n: spec((8,), jnp.float64)
                                       for n in fastsim._PARAM_FIELDS})
        geom = [spec((), jnp.int64)] * 4
        return fastsim._compiled(n_panels_max, 4, 4, "params").lower(
            *geom, prm).compile()


def test_fastsim_params_core_compiles_in_float64(spec):
    c = _fastsim_params_core(spec, 32)
    assert c.memory_analysis() is not None


def test_fastsim_params_core_compiles_in_blocks(spec):
    # 512 panels: two blocks of 256, the block loop around the panel loop
    from repro.core import fastsim
    assert fastsim._block_size(512) == 256
    c = _fastsim_params_core(spec, 512)
    assert c.memory_analysis() is not None


def test_fastsim_node_aware_params_core_compiles(spec):
    # the node-aware program: the node block and the lanes' intra-node
    # latency after the params, the node tables (hpl.nodes) in the
    # block's table pass
    from repro.core import fastsim
    with jax.enable_x64(True):
        prm = fastsim.FastSimParams(**{n: spec((8,), jnp.float64)
                                       for n in fastsim._PARAM_FIELDS})
        geom = [spec((), jnp.int64)] * 6
        c = fastsim._compiled(512, 4, 6, "params", True).lower(
            *geom[:4], prm, *geom[4:], spec((8,), jnp.float64)).compile()
    assert c.memory_analysis() is not None
