"""Share of the device's grid work that is live: the sum over answered
scenarios of panels x P x Q of their own runs, over the lanes the
program dispatched (``fastsim.lanes_live`` + ``lanes_padded``) times
the shape bucket's panels x P_max x Q_max (the ``bucket`` label of the
program's compile counters).  Counts lane and shape padding together.
Nothing is read when the window's dispatches used more than one bucket.
"""
import math

from harness import buckets


def read(run):
    s = run.window_stats
    lanes = s.get("fastsim.lanes_live", 0) + s.get("fastsim.lanes_padded", 0)
    b = buckets(s)
    if not lanes or len(b) != 1:
        return None
    live = sum(run.entry.live_work(w.wave, w.answers) for w in run.waves
               if w.answers is not None)
    return live / (lanes * math.prod(b.pop()))
