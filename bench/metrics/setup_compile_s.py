"""Seconds JAX spent compiling during set-up, by its own duration events:
tracing, lowering to MLIR, and the backend compile or persistent-cache
load."""
from harness import COMPILE_EVENTS


def read(run):
    return sum(secs for name, secs in run.events["setup"]
               if name in COMPILE_EVENTS)
