"""jit'd waterfilling using the Pallas masked-row-min kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .kernel import masked_min_rows, INF
from .ref import waterfill_ref


@partial(jax.jit, static_argnames=("max_iters", "use_kernel", "interpret"))
def waterfill(adj, caps, max_iters: int = 64, use_kernel: bool = True,
              interpret: bool = False):
    """Max-min fair rates via progressive filling; with ``use_kernel`` the
    per-iteration masked row-min runs through the Pallas kernel, which
    needs F % 8 == 0 and L % 128 == 0 (``interpret=True`` runs it in
    Python, off the chip)."""
    F, L = adj.shape
    adjf = adj.astype(jnp.float32)
    if use_kernel and (F % 8 or L % 128):
        raise ValueError(f"waterfill kernel needs F % 8 == 0 and "
                         f"L % 128 == 0, got F={F}, L={L}; pass "
                         "use_kernel=False for the jnp path")

    def minrows(share):
        if use_kernel:
            return masked_min_rows(adj, share, bf=min(256, F),
                                   bl=min(256, L), interpret=interpret)
        return jnp.min(jnp.where(adj > 0, share[None, :], INF), axis=1)

    def body(state):
        rates, frozen, rem, it = state
        active = 1.0 - frozen
        nl = adjf.T @ active
        share = jnp.where(nl > 0, rem / jnp.maximum(nl, 1.0), INF)
        fmin = minrows(share)
        fmin = jnp.where(active > 0, fmin, INF)
        smin = jnp.min(fmin)
        freeze_now = (jnp.abs(fmin - smin) <= 1e-6 * smin) & (active > 0)
        new_rates = jnp.where(freeze_now, smin, rates)
        used = adjf.T @ jnp.where(freeze_now, smin, 0.0)
        return (new_rates, frozen + freeze_now.astype(jnp.float32),
                jnp.maximum(rem - used, 0.0), it + 1)

    def cond(state):
        _, frozen, _, it = state
        return (it < max_iters) & (jnp.sum(frozen) < F)

    state = (jnp.zeros((F,), jnp.float32), jnp.zeros((F,), jnp.float32),
             caps.astype(jnp.float32), jnp.asarray(0))
    rates, _, _, _ = jax.lax.while_loop(cond, body, state)
    return jnp.where(jnp.sum(adj, axis=1) == 0, INF, rates)
