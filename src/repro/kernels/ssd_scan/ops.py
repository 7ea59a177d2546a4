"""jit'd public wrapper for the SSD chunk-scan kernel."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import ssd_scan


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd(xh, dt, A, Bh, Ch, chunk: int = 256, interpret: bool = False):
    """See kernel.ssd_scan.  Compiles for the TPU; ``interpret=True``
    runs the kernel body in Python (off-chip correctness checks)."""
    return ssd_scan(xh, dt, A, Bh, Ch, chunk, interpret=interpret)
