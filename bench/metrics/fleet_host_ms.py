"""Host time of fleet tuning and calibration per wave, in ms: the
program's ``fleet.phase_wall_s`` spans of the ``tune`` and
``calibrate`` phases over the window, over the ``predict_fleet`` calls
that timed their tuning."""

TUNE = 'fleet.phase_wall_s{phase="tune"}'
CALIBRATE = 'fleet.phase_wall_s{phase="calibrate"}'


def read(run):
    s = run.window_stats
    calls = s.get(TUNE + ".count", 0)
    if not calls:
        return None
    return 1e3 * (s.get(TUNE + ".sum", 0.0)
                  + s.get(CALIBRATE + ".sum", 0.0)) / calls
