"""Persistent XLA compilation cache for the repo's entry points.

A cold run of the fast models is dominated by compiling them: the HPL
panel recurrence takes tens of seconds to compile for a TPU, whatever
the problem size, and runs in about a second.  JAX's persistent cache
keeps compiled programs on disk across processes.  Its key includes the
cache directory, so the directory must not move between runs.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``python -m
repro.serve``, ``python -m repro.campaign`` and the examples) call
``enable_compile_cache()`` once before their first compile.  Tests do
not: they compile small programs and must not share state on disk.
"""
from __future__ import annotations

import os
from pathlib import Path

#: ``<checkout>/.jax_cache`` (git ignores it): a fixed path, never built
#: from a temp name, a pid or the time, so a later run of the same
#: checkout finds what an earlier one compiled
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and that directory stands; nothing else is set.  Otherwise the cache
    goes to ``CHECKOUT_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
