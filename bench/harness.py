"""Run one cell of ``BENCHMARK.json`` once and print its result line.

Everything a cell is made of is found by name under the benchmark's
directory, so a later cell is new files and new entries, not edits:

  configs/<config>.json   the deployment: its machines (platform
                          records, or list rows and the rules that
                          infer them), its source, and the check's limit
  traffic/<traffic>.json  the mix, read by ``generator.Traffic``; its
                          ``entry`` names the driver below
  entries/<entry>.py      the client of one service entry point and the
                          reference comparison of what it answers
  metrics/<metric>.py     one reader per metric: ``read(run)`` returns
                          the number, or None where it finds nothing

A run: set-up (imports, the persistent compile cache, the cell's
machines, one unscaled warm-up wave, which also gives the accuracy
against the published Rmax), then a closed loop of waves from the
traffic's clients for ``--seconds``, then the check of a seeded sample
of what the window answered against the plain reference
(``reference.py``), run once the window has closed.  ``--trace 1``
turns on the program's counters, the benchmark's host spans and the
profiler, and reports the per-layer metrics instead of the end-to-end
ones.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: JAX's monitoring events that make up a compile: tracing, lowering,
#: and the backend compile (which includes a persistent-cache load)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class WaveRecord:
    wave: list                 # [(machine index, {knob: scale})]
    answers: Any               # what the entry returned; None if failed
    t0: float
    t1: float
    failed: int
    stats: Dict[str, float]    # program counter and span deltas (traced,
                               # one client)
    index: int = 0             # the wave's place in the traffic's order

    @property
    def n(self) -> int:
        return len(self.wave)


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    cell: dict
    config: dict
    traffic: dict
    entry: Any
    setup_s: float = 0.0
    rmax_err_pct: float = float("nan")
    waves: List[WaveRecord] = dataclasses.field(default_factory=list)
    window_s: float = 0.0
    events: Dict[str, list] = dataclasses.field(
        default_factory=lambda: {"setup": [], "window": []})
    traces: int = 0            # programs the fast models traced in window
    window_stats: Dict[str, float] = dataclasses.field(default_factory=dict)
    trace: Optional[dict] = None   # the profiler trace's reduction


class JaxEvents:
    """JAX's monitoring events, filed under the phase of the run."""

    def __init__(self, run: Run):
        self.run = run
        self.phase = "setup"

    def event(self, name: str, **_):
        self.run.events[self.phase].append((name, 0.0))

    def duration(self, name: str, secs: float, **_):
        self.run.events[self.phase].append((name, float(secs)))


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: Path):
    """Import a file of the benchmark by path, under a name of its own."""
    name = "bench_" + "_".join(path.relative_to(path.parents[1]).with_suffix(
        "").parts).replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def by_name(items: List[dict], name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise SystemExit(f"bench: no {what} named {name!r} in BENCHMARK.json "
                     f"(have {[it['name'] for it in items]})")


def load_cell(root: Path, workload: str):
    """(spec, cell, config, traffic, entry) of a cell, found by name."""
    spec = load_json(root / "BENCHMARK.json")
    cell = by_name(spec["workloads"], workload, "workload")
    config = load_json(root / by_name(spec["configs"], cell["config"],
                                      "config")["file"])
    traffic = load_json(root / "bench" / "traffic"
                        / f"{cell['traffic']}.json")
    entry = load_module(root / "bench" / "entries"
                        / f"{traffic['entry']}.py").Entry(config, traffic)
    return spec, cell, config, traffic, entry


def cell_metrics(spec: dict, cell: str, traced: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with tracing its per-layer metrics."""
    group = spec["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def require_chip(devices, chips: int) -> None:
    if devices[0].platform == "cpu":
        raise NoChip(f"needs an accelerator, but JAX found only "
                     f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found "
                     f"{len(devices)}")


def _stats(reg) -> Dict[str, float]:
    """Flat view of a metrics registry: counters, and histogram sums and
    counts (``<key>.sum``, ``<key>.count``)."""
    snap = reg.snapshot()
    out = dict(snap["counters"])
    for key, h in snap["histograms"].items():
        out[key + ".sum"] = h["sum"]
        out[key + ".count"] = h["count"]
    return out


def buckets(stats: Dict[str, float]) -> set:
    """Shape buckets (n_panels_max, P_max, Q_max) of the fast model's
    dispatches, from the ``bucket`` label of its compile counters."""
    out = set()
    for key in stats:
        for p in ('fastsim.compile_hits{bucket="',
                  'fastsim.compile_misses{bucket="'):
            if key.startswith(p):
                out.add(tuple(int(x) for x in key[len(p):-2].split("x")))
    return out


def _delta(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
    return {k: v - a.get(k, 0.0) for k, v in b.items() if v != a.get(k, 0.0)}


def _span(traced: bool, name: str):
    if not traced:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


@contextlib.contextmanager
def spans(targets):
    """Wrap callables in profiler host spans for the traced run, and put
    them back after: ``targets`` is ``[(owner, attribute, span name)]``,
    the owner a module or an object."""
    import jax
    saved = []

    def wrap(fn, name):
        @functools.wraps(fn)
        def spanned(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        return spanned

    try:
        for owner, attr, name in targets:
            saved.append((owner, attr, owner.__dict__.get(attr)))
            setattr(owner, attr, wrap(getattr(owner, attr), name))
        yield
    finally:
        for owner, attr, orig in reversed(saved):
            if orig is None:
                delattr(owner, attr)
            else:
                setattr(owner, attr, orig)


def process_pool(workers: int):
    """Processes for the reference (it holds no chip), or None."""
    if workers <= 1:
        return None
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor
    return ProcessPoolExecutor(workers,
                               mp_context=multiprocessing.get_context(
                                   "spawn"))


def sample(run: Run, seed: int) -> list:
    """The (input, answer) pairs the check compares: one at each position
    of a wave's samples (for a wave of requests, each lane), drawn from
    the seed among the window's answered waves, so that a fault in any
    one lane is compared in every run."""
    import numpy as np
    by_pos: Dict[int, list] = {}
    for w in run.waves:
        if w.answers is not None:
            for pos, unit in enumerate(run.entry.samples(w.wave,
                                                         w.answers)):
                by_pos.setdefault(pos, []).append(unit)
    rng = np.random.default_rng([seed, 1])
    return [by_pos[pos][rng.integers(len(by_pos[pos]))]
            for pos in sorted(by_pos)]


def check(run: Run, seed: int, workers: int) -> Dict[str, dict]:
    """Compare a seeded sample of the window's answers with the plain
    reference; every number with its limit."""
    import numpy as np
    entry = run.entry
    chosen = sample(run, seed)
    gap = None                  # no answer to compare
    if chosen:
        pool = process_pool(min(workers, len(chosen), os.cpu_count() or 1))
        try:
            expected = entry.expected([u[0] for u in chosen], np.float64,
                                      pool.map if pool else map)
        finally:
            if pool is not None:
                pool.shutdown(wait=True)
        gap = float(max(entry.rel_gap(u[1], e)
                        for u, e in zip(chosen, expected)))
    failed = sum(w.failed for w in run.waves)
    wave = len(entry.samples(run.waves[0].wave, [None] * run.waves[0].n))
    return {"max_rel_gap": {"value": gap,
                            "limit": run.config["check"]["max_rel_gap"]},
            "samples": {"value": len(chosen), "limit": wave},
            "failed": {"value": failed, "limit": 0}}


def passed(compared: Dict[str, dict]) -> bool:
    gap = compared["max_rel_gap"]
    return (gap["value"] is not None and gap["value"] <= gap["limit"]
            and compared["samples"]["value"] == compared["samples"]["limit"]
            and compared["failed"]["value"] == 0)


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             root: Path = ROOT, chip: bool = True,
             persistent_cache: bool = True, workers: int = 8,
             t_start: Optional[float] = None, out=None, err=None,
             place=None) -> int:
    """One run; prints the result line and returns the exit code.
    ``place(entry)``, where given, puts something in the program's place
    before set-up (the control: the reference one precision down)."""
    t_start = time.perf_counter() if t_start is None else t_start
    out = out or sys.stdout
    err = err or sys.stderr
    spec, cell, config, traffic, entry = load_cell(root, workload)
    if place is not None:
        place(entry)
    readers = {m["name"]: load_module(root / "bench" / "metrics"
                                      / f"{m['name']}.py")
               for m in cell_metrics(spec, workload, traced)}

    src = str(root / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    devices = jax.devices()
    if chip:
        try:
            require_chip(devices, cell["chips"])
        except NoChip as exc:
            print(f"bench: {exc}", file=err)
            return 3
    if persistent_cache:
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        # cache every program, however quick, so that set-up is steady
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from generator import Traffic
    from repro import workloads
    from repro.core import fastsim
    from repro.obs import MetricsRegistry, global_metrics

    run = Run(cell=cell, config=config, traffic=traffic, entry=entry)
    events = JaxEvents(run)
    jax.monitoring.register_event_listener(events.event)
    jax.monitoring.register_event_duration_secs_listener(events.duration)
    registry = MetricsRegistry() if traced else None
    try:
        entry.setup(metrics=registry)
        gen = Traffic(traffic, len(entry.machines), seed)
        warm = gen.unscaled_wave()
        run.rmax_err_pct = entry.rmax_err_pct(
            warm, entry.serve(entry.build(warm)))
        run.setup_s = time.perf_counter() - t_start

        events.phase = "window"
        traces0 = fastsim.trace_count() + workloads.trace_count()
        profiler = Profiler(int(traffic.get("trace_waves", 1))) if traced \
            else None
        off = contextlib.nullcontext()
        with (global_metrics(registry) if traced else off), \
                (entry.annotate() if traced else off):
            try:
                _window(run, gen, seconds, traced, registry, profiler)
            finally:
                if traced:
                    profiler.stop()
        run.traces = fastsim.trace_count() + workloads.trace_count() \
            - traces0
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices[:cell["chips"]])
        if traced:
            from xplane import reduce_trace
            run.trace = reduce_trace(profiler.dir, Profiler.SPAN)
            shutil.rmtree(profiler.dir, ignore_errors=True)
    finally:
        jax.monitoring.unregister_event_listener(events.event)
        jax.monitoring.unregister_event_duration_listener(events.duration)

    metrics = {}
    for m in cell_metrics(spec, workload, traced):
        value = readers[m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif not traced:
            print(f"bench: end-to-end metric {m['name']} read nothing",
                  file=err)
            return 4

    compared = check(run, seed, workers)
    ok = passed(compared)
    line: Dict[str, Any] = {
        "correct": bool(ok),
        "attempted": sum(w.n for w in run.waves),
        "failed": sum(w.failed for w in run.waves),
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices), "memory_peak_bytes": peak},
    }
    if run.trace is not None:
        line["device"]["busy_s"] = run.trace["busy_s"]
        line["device"]["window_s"] = run.trace["window_s"]
        line["breakdown"] = run.trace["breakdown"]
    line["check"] = compared
    print(f"bench: {workload} seed {seed}: {len(run.waves)} waves in "
          f"{run.window_s:.3f} s, set-up {run.setup_s:.3f} s", file=err)
    for name, c in compared.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err)
    print(json.dumps(line), file=out)
    return 0


class Profiler:
    """JAX's profiler over the window's first ``waves`` waves: a short
    stretch keeps the trace small, and its reading quick."""
    SPAN = "bench.traced"

    def __init__(self, waves: int):
        self.waves = waves
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self._span = None

    def start(self) -> None:
        import jax
        jax.profiler.start_trace(self.dir)
        self._span = jax.profiler.TraceAnnotation(self.SPAN)
        self._span.__enter__()

    def timed_stop(self) -> float:
        """Stop; returns the seconds that took (writing the trace), which
        the window does not count."""
        t0 = time.perf_counter()
        self.stop()
        return time.perf_counter() - t0

    def stop(self) -> None:
        if self._span is not None:
            import jax
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()


def _window(run: Run, gen, seconds: float, traced: bool, registry,
            profiler: Optional[Profiler]) -> None:
    """The closed loop: each of the traffic's ``clients`` sends its next
    wave when its last is answered, and none is sent after ``seconds``.
    The window closes when every wave sent is answered: all of that work
    counts, over all of that time.  With more than one client a wave
    waits on the device behind another client's, which keeps the chip
    fed while a client reads its answer and builds its next wave.

    The profiler covers the first ``profiler.waves`` waves.  Once they
    are answered no wave is sent until the waves in flight are answered
    too and the profiler has stopped, so that the trace holds whole
    waves and its writing is left out of the window."""
    entry = run.entry
    clients = int(run.traffic.get("clients", 1))
    per_wave = traced and clients == 1  # counters of one wave alone
    cond = threading.Condition()
    # paused: the profiler writing; hold: no wave is sent until it is off
    state = {"sent": 0, "in_flight": 0, "ended": 0, "paused": 0.0,
             "hold": False}
    errors: List[BaseException] = []

    def client() -> None:
        try:
            while True:
                with cond:
                    cond.wait_for(lambda: not state["hold"])
                    if state["sent"] and time.perf_counter() - t_win \
                            - state["paused"] >= seconds:
                        return
                    index = state["sent"]
                    state["sent"] += 1
                    state["in_flight"] += 1
                    with _span(traced, "bench.generate"):
                        wave = gen.next_wave()
                        built = entry.build(wave)
                before = _stats(registry) if per_wave else None
                t0 = time.perf_counter()
                try:
                    with _span(traced, "bench.wave"):
                        answers = entry.serve(built)
                    failed = 0
                except Exception:       # a failed wave counts, not ends
                    traceback.print_exc()
                    answers, failed = None, len(wave)
                t1 = time.perf_counter()
                with cond:
                    run.waves.append(WaveRecord(
                        wave, answers, t0, t1, failed,
                        _delta(before, _stats(registry)) if per_wave
                        else {}, index))
                    state["in_flight"] -= 1
                    if profiler is not None \
                            and len(run.waves) == profiler.waves:
                        state["hold"] = True
                    cond.notify_all()
        except BaseException as exc:    # re-raised by the window
            errors.append(exc)
        finally:
            with cond:
                state["ended"] += 1
                cond.notify_all()

    before_all = _stats(registry) if traced else None
    threads = [threading.Thread(target=client, name=f"client-{i}")
               for i in range(clients)]
    if profiler is not None:
        profiler.start()
    t_win = time.perf_counter()
    with _span(traced, "bench.window"):
        for t in threads:
            t.start()
        if profiler is not None:
            with cond:
                cond.wait_for(lambda: state["ended"] == clients or (
                    state["hold"] and not state["in_flight"]))
                state["paused"] += profiler.timed_stop()
                state["hold"] = False
                cond.notify_all()
        for t in threads:
            t.join()
    if errors:
        raise errors[0]
    run.waves.sort(key=lambda w: w.index)
    run.window_s = max(w.t1 for w in run.waves) - t_win - state["paused"]
    if traced:
        run.window_stats = _delta(before_all, _stats(registry))
