"""Host side of the compiled call per dispatch, in ms: the program's
``fastsim.launch`` span (``fn(*args)`` until it returns: argument
conversion, host-to-device transfer and enqueue), the window's
``fastsim.launch_s`` sum over its count."""

LAUNCH = "fastsim.launch_s"


def read(run):
    s = run.window_stats
    calls = s.get(LAUNCH + ".count", 0)
    if not calls:
        return None
    return 1e3 * s[LAUNCH + ".sum"] / calls
