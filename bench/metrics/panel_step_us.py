"""Device time of one panel step of the HPL recurrence, in us: the
device time of the program that took most of it in the traced window
(the recurrence: each cell runs one fast-model family), over its
executions times the bucket's panel count (the ``bucket`` label of the
program's compile counters)."""
from harness import buckets


def read(run):
    if run.trace is None or not run.trace["modules"]:
        return None
    b = buckets(run.window_stats)
    if len(b) != 1:
        return None
    n_panels = b.pop()[0]
    count, secs = max(run.trace["modules"].values(), key=lambda cs: cs[1])
    return 1e6 * secs / (count * n_panels)
