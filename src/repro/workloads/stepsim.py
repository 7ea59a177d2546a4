"""stepsim — the transformer train step as a batched JAX program.

The fastsim idea (DESIGN.md §10-11) applied to the second application:
where fastsim vectorizes HPL's panel recurrence, this module vectorizes
the train-step schedule the DES app (core/apps/transformer.py) walks
event by event — per-layer roofline compute, ring collectives on the
model axis, a tail gradient ring on the data axis, and a cross-pod DCN
ring when the job spans pods.

``StepParams`` is a frozen dataclass registered as a pytree: every leaf
is *traced*, so model-size x mesh x platform what-if grids never
recompile — ``sweep_step`` pads the scenario batch to a power of two and
runs it as ONE compiled program with a leading batch axis, exactly the
sweep-engine contract ``sweep_hpl`` gives HPL.  ``jax.grad`` flows
through ``step_time_traced`` for calibration parity with
``calibrate.fit_fastsim_params``.

The closed forms mirror the DES timing model, not an idealized one:
ring rounds serialize at ``per_round/bw + phase_latency`` where
``phase_latency`` is the DES's per-message cost (MPI overhead +
rendezvous handshakes + hop latency), so DES-vs-stepsim
cross-validation holds the same way DES-vs-fastsim does for HPL.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fastsim import _call, _pad_lanes, _shard_lanes
from repro.obs.metrics import Timer, get_global_metrics


@dataclasses.dataclass(frozen=True)
class StepParams:
    """One train-step scenario; every field is a traced pytree leaf.

    Group sizes are floats so the whole scenario — including the mesh —
    can ride the batch axis; bytes fields follow the DES wire convention
    (bytes moved through one device over the whole ring).
    """
    # chip (per rank)
    peak_flops: float
    gemm_eff: float
    mem_bw: float
    mem_eff: float
    # fabric
    link_bw: float               # B/s per ICI link per direction
    phase_latency: float         # per ring-round message cost (s)
    pod_bw: float = 25e9         # effective per-flow cross-pod B/s
    pod_latency: float = 10e-6   # per cross-pod round latency (s)
    # per-chip workload (derived from the model dims by the workload)
    flops_per_layer: float = 0.0
    bytes_per_layer: float = 0.0
    coll_model_bytes: float = 0.0   # ring wire bytes per layer, model axis
    coll_data_bytes: float = 0.0    # tail ring wire bytes, data axis
    n_layers: float = 1.0
    model_group: float = 1.0
    data_group: float = 1.0
    pod_group: float = 1.0
    overlap: float = 0.0         # fraction of comm hidden under compute


_STEP_FIELDS = tuple(f.name for f in dataclasses.fields(StepParams))

jax.tree_util.register_dataclass(
    StepParams, data_fields=list(_STEP_FIELDS), meta_fields=[])


def _f64_step_params(p: StepParams) -> StepParams:
    return StepParams(**{n: float(getattr(p, n)) for n in _STEP_FIELDS})


def _ring(wire_bytes, group, bw, latency):
    """Ring-collective time under the DES schedule: the wire bytes
    stream at the link rate while 2(n-1) rounds each pay the per-message
    latency; groups of one collapse to zero."""
    rounds = 2.0 * (group - 1.0)
    t = wire_bytes / bw + rounds * latency
    return jnp.where(group > 1.0, t, 0.0)


def _step_core(p: StepParams):
    """Traced step time; all leaves scalar or (B,)-batched."""
    compute = jnp.maximum(
        p.flops_per_layer / (p.peak_flops * p.gemm_eff),
        p.bytes_per_layer / (p.mem_bw * p.mem_eff))
    coll = _ring(p.coll_model_bytes, p.model_group, p.link_bw,
                 p.phase_latency)
    # overlap=0 reproduces the DES's serial schedule; >0 models async
    # collectives hidden under compute (the SimXLA overlap knob)
    layer = jnp.maximum(compute, coll) \
        + (1.0 - p.overlap) * jnp.minimum(compute, coll)
    tail = _ring(p.coll_data_bytes, p.data_group, p.link_bw,
                 p.phase_latency)
    # cross-pod ring: the DES rings wire/data_group bytes over the pod
    # group through the pod gateways
    pod_wire = p.coll_data_bytes / jnp.maximum(p.data_group, 1.0)
    pod = _ring(pod_wire, p.pod_group, p.pod_bw, p.pod_latency)
    return p.n_layers * layer + tail + pod


# --------------------------------------------------------- compile cache
_TRACE_COUNT = 0


def trace_count() -> int:
    """How many times the step core has been (re)traced — compile-once
    assertions for tests and benchmarks (mirrors fastsim.trace_count)."""
    return _TRACE_COUNT


@functools.lru_cache(maxsize=4)
def _compiled():
    def transformer_step(p):
        global _TRACE_COUNT
        _TRACE_COUNT += 1
        return _step_core(p)
    return jax.jit(transformer_step)


def step_time_traced(p: StepParams):
    """Differentiable scalar step time for traced ``p`` leaves (call
    under ``jax.enable_x64(True)``) — the autodiff surface for
    gradient calibration of step parameters."""
    return _step_core(p)


def _stack_step_params(prm_list: Sequence[StepParams],
                       lanes: Sequence[int]) -> StepParams:
    return StepParams(**{
        n: np.asarray([float(getattr(prm_list[i], n)) for i in lanes],
                      np.float64)
        for n in _STEP_FIELDS})


def _result(p: StepParams, t: float) -> Dict:
    flops = p.n_layers * p.flops_per_layer
    return {"time_s": t, "step_s": t,
            "mfu": flops / max(t, 1e-30) / p.peak_flops}


def sweep_step(params_list: Sequence[StepParams], *,
               shard: bool = False) -> List[Dict]:
    """Run a step-scenario sweep as one compiled batched program.

    The batch is padded to a power of two so repeat sweeps of any size
    reuse the compile cache; results come back in input order as dicts
    with ``time_s``/``step_s``/``mfu`` (model-level fields like
    tokens/s are layered on by ``TransformerWorkload``).  ``shard=True``
    splits the padded lane axis across local devices, as in
    ``sweep_hpl``.
    """
    prm_list = [_f64_step_params(p) for p in params_list]
    if not prm_list:
        return []
    m = get_global_metrics()
    pre = trace_count()
    with jax.enable_x64(True):
        with (Timer(span="stepsim.prepare") if m.enabled
              else contextlib.nullcontext()) as prep:
            lanes = _pad_lanes(list(range(len(prm_list))), shard)
            (stacked,), sharded = _shard_lanes(
                shard, len(lanes), _stack_step_params(prm_list, lanes))
        # fastsim's dispatch taxonomy under the stepsim.* prefix, one
        # shared "step" bucket (the step core is shape-monomorphic)
        out = _call(_compiled(), (stacked,), ("step",), len(prm_list),
                    len(lanes), sharded, prefix="stepsim",
                    traces=trace_count)
    if m.enabled and trace_count() == pre:
        m.histogram("stepsim.prepare_s").observe(prep.elapsed)
    return [_result(p, float(t))
            for p, t in zip(prm_list, out[:len(prm_list)])]


def simulate_step_fast(p: StepParams) -> Dict:
    """Single-scenario convenience over ``sweep_step``."""
    return sweep_step([p])[0]
