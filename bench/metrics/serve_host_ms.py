"""Host time of the serving front end per wave, in ms: each wave's
client-side time less the fast model's dispatch time in that wave
(``fastsim.dispatch_wall_s``, the program's span around the compiled
call), averaged over the window's waves."""

DISPATCH = "fastsim.dispatch_wall_s.sum"


def read(run):
    waves = [w for w in run.waves if DISPATCH in w.stats]
    if not waves:
        return None
    return 1e3 * sum((w.t1 - w.t0) - w.stats[DISPATCH]
                     for w in waves) / len(waves)
