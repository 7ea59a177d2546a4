"""Error against the published Rmax, in %, of the unscaled warm-up wave
of set-up, as the cell's entry defines it (one machine: |predicted -
Rmax| / Rmax; a fleet: the held-out median after calibration)."""


def read(run):
    return run.rmax_err_pct
