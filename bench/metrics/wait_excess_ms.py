"""Wait on the compiled call beyond the device's execution, per
dispatch, in ms: the mean of the program's ``fastsim.wait`` span over
the window, less the device time per execution of the recurrence
program in the traced waves (the program that took most device time,
as ``panel_step_us`` takes it).  What is left is the queue before the
execution and the answer's return to the host.  Read only where one
client sends waves: with two, the wait holds the other client's wave."""

WAIT = "fastsim.wait_s"


def read(run):
    s = run.window_stats
    calls = s.get(WAIT + ".count", 0)
    if not calls or run.trace is None or not run.trace["modules"]:
        return None
    count, secs = max(run.trace["modules"].values(), key=lambda cs: cs[1])
    return 1e3 * (s[WAIT + ".sum"] / calls - secs / count)
