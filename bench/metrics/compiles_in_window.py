"""Programs compiled inside the window: the fast models' own trace
counters (``fastsim.trace_count``, ``workloads.trace_count``) plus
JAX's persistent-cache misses.  0 on a warm path."""
from harness import CACHE_MISS_EVENT


def read(run):
    misses = sum(1 for name, _ in run.events["window"]
                 if name == CACHE_MISS_EVENT)
    return float(run.traces + misses)
