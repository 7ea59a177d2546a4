"""Seconds from the start of the process's script to the first timed
wave: imports, the device, the compile cache, the machines and the
warm-up wave (host clock)."""


def read(run):
    return run.setup_s
