"""Reduce a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
In it, each accelerator is a plane ``/device:<KIND>:<n>`` whose line
``XLA Ops`` holds one event per operation the device ran and whose line
``XLA Modules`` holds one event per program execution; the host is the
plane ``/host:CPU``, whose lines hold the spans the benchmark put around
its calls (``jax.profiler.TraceAnnotation``).  All times are on one
clock, in nanoseconds.

``reduce_trace`` takes the window from the host span of that name and
returns, within it:

* ``busy_s``: the union of the intervals in which an operation ran,
  averaged over the devices that ran any;
* ``window_s``: the window's length;
* ``modules``: program name -> (executions, device seconds);
* ``breakdown``: the ten operations that took most device time of
  their own (an op's time less that of the ops nested in it, so a
  ``while`` op keeps only the loop's own overhead), named by their HLO
  instruction; and the device's idle time split by the innermost
  benchmark or program span the host was in (``idle`` where it was in
  none), ten largest.
"""
from __future__ import annotations

from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans that name what the host was doing (the benchmark's own)
SPAN_PREFIXES = ("bench.", "serve.", "fleet.")
TOP = 10


def _union(starts: np.ndarray, ends: np.ndarray
           ) -> Tuple[float, np.ndarray, np.ndarray]:
    """Length of the union of [start, end) intervals, and the gaps
    between its pieces (as start and end arrays)."""
    if not len(starts):
        return 0.0, starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    # a new piece starts where an interval begins after all before it end
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    piece_s = s[new]
    piece_e = np.append(reach[np.flatnonzero(new)[1:] - 1], reach[-1])
    return (float(np.sum(piece_e - piece_s)), piece_e[:-1], piece_s[1:])


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _self_times(st: np.ndarray, en: np.ndarray) -> np.ndarray:
    """Each interval's length less the intervals nested directly in it
    (the device's ops nest: a loop op holds its body's ops)."""
    order = np.lexsort((-en, st))         # by start, the longer first
    own = (en - st).astype(np.float64)
    stack: List[int] = []
    for i in order.tolist():
        while stack and en[stack[-1]] <= st[i]:
            stack.pop()
        if stack:
            own[stack[-1]] -= min(en[i], en[stack[-1]]) - st[i]
        stack.append(i)
    return own


def _gap_activity(gap_s: np.ndarray, gap_e: np.ndarray,
                  spans: List[Tuple[str, int, int]]) -> Dict[str, float]:
    """Idle seconds by the innermost host span around each gap's middle."""
    if not len(gap_s):
        return {}
    mid = (gap_s + gap_e) / 2.0
    label = np.zeros(len(mid), np.int64)       # 0: no span
    names = ["idle"]
    # longest first, so that an inner span overrides the one around it
    for name, s0, s1 in sorted(spans, key=lambda sp: sp[1] - sp[2]):
        lo, hi = np.searchsorted(mid, [s0, s1])
        if hi > lo:
            if name not in names:
                names.append(name)
            label[lo:hi] = names.index(name)
    secs = np.bincount(label, weights=(gap_e - gap_s) / 1e9,
                       minlength=len(names))
    return {n: float(v) for n, v in zip(names, secs) if v > 0}


def xplane_file(trace_dir) -> Path:
    files = sorted(Path(trace_dir).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_trace(trace_dir, window: str = "bench.window") -> Optional[dict]:
    """The device numbers of the traced window, or None when the trace
    holds no such window or no device that ran an operation in it."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(xplane_file(trace_dir)))
    spans: List[Tuple[str, int, int]] = []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/device:"):
            devices.append({line.name: [(ev.name, ev.start_ns,
                                         ev.duration_ns)
                                        for ev in line.events]
                            for line in plane.lines
                            if line.name in (OPS_LINE, MODULES_LINE)})
    wins = [(s0, s1) for name, s0, s1 in spans if name == window]
    if not wins:
        return None
    w0, w1 = min(s for s, _ in wins), max(e for _, e in wins)
    inside = [sp for sp in spans if sp[0] != window]
    return reduce_events(devices, inside, w0, w1)


def reduce_events(devices: List[Dict[str, list]],
                  spans: List[Tuple[str, int, int]], w0: int, w1: int
                  ) -> Optional[dict]:
    """``reduce_trace`` on events already read: per device, line name ->
    [(name, start_ns, duration_ns)]; host spans as (name, start, end)."""
    busy, ops, idle = [], {}, {}
    modules: Dict[str, List[float]] = {}
    for dev in devices:
        evs = dev.get(OPS_LINE, [])
        if not evs:
            continue
        names = [e[0] for e in evs]
        st = np.asarray([e[1] for e in evs], np.float64)
        en = st + np.asarray([e[2] for e in evs], np.float64)
        keep = (en > w0) & (st < w1)
        st, en = np.clip(st[keep], w0, w1), np.clip(en[keep], w0, w1)
        if not len(st):
            continue
        length, gap_s, gap_e = _union(st, en)
        # the stretches before the first and after the last operation
        gap_s = np.concatenate([[w0], gap_s, [en.max()]])
        gap_e = np.concatenate([[st.min()], gap_e, [w1]])
        busy.append(length)
        for name, secs in _gap_activity(gap_s, gap_e, spans).items():
            idle[name] = idle.get(name, 0.0) + secs
        kept = [op_name(n) for n, k in zip(names, keep) if k]
        for name, own in zip(kept, _self_times(st, en)):
            ops[name] = ops.get(name, 0.0) + own / 1e9
        for name, s, d in dev.get(MODULES_LINE, []):
            if s + d > w0 and s < w1:
                cs = modules.setdefault(name, [0, 0.0])
                cs[0] += 1
                cs[1] += (min(s + d, w1) - max(s, w0)) / 1e9
    if not busy:
        return None
    n = len(busy)
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:TOP]
    return {"busy_s": sum(busy) / n / 1e9,
            "window_s": (w1 - w0) / 1e9,
            "modules": {k: (int(c), s) for k, (c, s) in modules.items()},
            "breakdown": {"device_ops": [[k, v / n] for k, v in top],
                          "idle_gaps": [[k, v / n] for k, v in gaps]}}
