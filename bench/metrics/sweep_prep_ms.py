"""Host time of sweep assembly per ``sweep_hpl`` call, in ms: the
program's ``fastsim.prepare`` span (grouping by geometry, shape bucket,
lane padding, stacking the parameters, lane sharding, the geometry
array), the window's ``fastsim.prepare_s`` sum over its count."""

PREPARE = "fastsim.prepare_s"


def read(run):
    s = run.window_stats
    calls = s.get(PREPARE + ".count", 0)
    if not calls:
        return None
    return 1e3 * s[PREPARE + ".sum"] / calls
