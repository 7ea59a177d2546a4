"""Share of the traced window in which no operation ran on the device,
in %: 1 - (union of the device's op intervals) / window, from the
profiler trace (``xplane.reduce_trace``)."""


def read(run):
    if run.trace is None or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
