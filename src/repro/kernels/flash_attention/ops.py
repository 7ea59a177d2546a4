"""jit'd public wrapper for the flash-attention kernel.

The kernel compiles via Mosaic, so it runs on a TPU.  ``interpret=True``
executes the kernel body in Python instead (correctness checks off the
chip); it is never chosen on the caller's behalf.
"""
from __future__ import annotations

from functools import partial

import jax

from .kernel import flash_attention_fwd


@partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q, k, v, causal: bool = True, bq: int = 128,
                    bk: int = 128, interpret: bool = False):
    """q: (B, Sq, G, R, hd); k, v: (B, Sk, G, hd) -> (B, Sq, G, R, hd)."""
    return flash_attention_fwd(q, k, v, causal=causal, bq=bq, bk=bk,
                               interpret=interpret)
