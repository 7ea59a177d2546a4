"""Scenarios answered in the window over the window's seconds (host clock).

The window runs from the first wave's generation to the last wave's
answer; a wave that failed answers nothing."""


def read(run):
    answered = sum(w.n - w.failed for w in run.waves)
    return answered / run.window_s
