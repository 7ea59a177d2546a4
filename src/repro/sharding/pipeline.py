"""Pipeline parallelism over the `pod` axis (GPipe-style).

Cross-pod DCN bandwidth (~25 GB/s/chip) is far below ICI (~200 GB/s/chip
aggregate), so the right multi-pod decomposition for big models is
pipeline stages across pods: only (B_micro, S, D) activations cross the
DCN, once per microbatch per stage boundary, instead of gradient
all-reduces of the full parameter set.

Implementation: ``shard_map`` over the `pod` axis; layer stacks are split
into `n_stages` contiguous stages (params sharded on the stage dim);
microbatches advance through a ``lax.scan`` whose carry rotates stage
outputs with ``ppermute``.  The standard GPipe schedule runs
(n_micro + n_stages - 1) ticks; bubble fraction = (S-1)/(M+S-1).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_forward(layer_fn: Callable, stage_params, x, *,
                     mesh: Mesh, axis: str = "pod", n_micro: int = 4):
    """Run x through all pipeline stages.

    layer_fn(params_stage, x_micro) -> x_micro : one stage's computation.
    stage_params: pytree with leading stage dim == mesh.shape[axis]
                  (sharded over `axis`).
    x: (B, ...) global batch, B % n_micro == 0.
    Returns y with x's shape — output of the final stage.
    """
    n_stages = mesh.shape[axis]

    def per_pod(params_local, x_local):
        # params_local: stage dim 1 (this pod's stage); x_local: full batch
        params_me = jax.tree.map(lambda a: a[0], params_local)
        stage_id = lax.axis_index(axis)
        b = x_local.shape[0]
        mb = b // n_micro
        micro = x_local.reshape((n_micro, mb) + x_local.shape[1:])
        n_ticks = n_micro + n_stages - 1
        pad = jnp.zeros((n_stages - 1, mb) + x_local.shape[1:],
                        x_local.dtype)
        feed = jnp.concatenate([micro, pad], axis=0)
        outs0 = jnp.zeros_like(feed)

        def tick(carry, t):
            buf, outs = carry     # buf: (mb, ...) activation entering me
            inject = feed[jnp.minimum(t, n_ticks - 1)]
            x_in = jnp.where(stage_id == 0, inject, buf)
            y = layer_fn(params_me, x_in)
            # pass to next stage (ring; last stage's output is collected)
            nxt = lax.ppermute(y, axis,
                               [(i, (i + 1) % n_stages)
                                for i in range(n_stages)])
            out_idx = t - (n_stages - 1)
            idx = jnp.clip(out_idx, 0, feed.shape[0] - 1)
            outs = jnp.where(out_idx >= 0, outs.at[idx].set(y), outs)
            return (nxt, outs), None

        buf0 = jnp.zeros((mb,) + x_local.shape[1:], x_local.dtype)
        # initial carry must already be pod-varying for scan type stability
        buf0 = lax.pcast(buf0, axis, to="varying")
        outs0 = lax.pcast(outs0, axis, to="varying")
        (_, outs), _ = lax.scan(tick, (buf0, outs0),
                                jnp.arange(n_ticks))
        # outs on the LAST stage holds the final microbatch outputs;
        # broadcast to all pods (masked psum — ppermute needs a bijection)
        outs = lax.psum(jnp.where(stage_id == n_stages - 1, outs, 0.0),
                        axis)
        return outs[:n_micro].reshape(x_local.shape)

    pspec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fwd = jax.shard_map(per_pod, mesh=mesh,
                        in_specs=(pspec_params, P()), out_specs=P())
    with jax.set_mesh(mesh):
        return jax.jit(fwd)(stage_params, x)


def pipeline_bubble_fraction(n_micro: int, n_stages: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
