"""90th percentile of the client-side wave latency over every wave of the
window, in ms (host clock; linear interpolation between order
statistics).  A failed wave counts with the time it took to fail.  With
two clients a wave's latency holds its wait on the device behind the
other client's wave."""
import numpy as np


def read(run):
    return 1e3 * float(np.percentile([w.t1 - w.t0 for w in run.waves], 90))
