"""Masked row-min over a flow x link incidence — Pallas TPU kernel.

This is the inner op of progressive-filling max-min fairness (the
stream-level network model's hot loop): for every flow, the minimum fair
share over the links it crosses.  Tiled (bf x bl) with a running-min VMEM
accumulator across link blocks; int8 incidence keeps the HBM footprint at
F x L bytes (100k flows x 8k links = 0.8 GB, streamable).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


INF = 3.4e38


def _minrows_kernel(adj_ref, vals_ref, out_ref, acc_ref, *, n_l_blocks):
    li = pl.program_id(1)

    @pl.when(li == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, INF)

    # widen int8 -> int32 and broadcast vals before the select: a mask
    # compared in int8 layout cannot be relaid out against (1, bl) rows
    adj = adj_ref[...].astype(jnp.int32)     # (bf, bl)
    vals = jnp.broadcast_to(vals_ref[...], adj.shape)
    masked = jnp.where(adj > 0, vals, INF)
    acc_ref[...] = jnp.minimum(acc_ref[...],
                               jnp.min(masked, axis=1, keepdims=True))

    @pl.when(li == n_l_blocks - 1)
    def _finish():
        out_ref[...] = acc_ref[...]


def masked_min_rows(adj, vals, *, bf: int = 256, bl: int = 256,
                    interpret: bool = False):
    """adj: (F, L) int8/bool; vals: (L,) f32 -> (F,) f32 row-min."""
    F, L = adj.shape
    bf = min(bf, F)
    bl = min(bl, L)
    assert F % bf == 0 and L % bl == 0, (F, bf, L, bl)
    nf, nl = F // bf, L // bl
    vals2 = vals.reshape(1, L).astype(jnp.float32)
    kernel = functools.partial(_minrows_kernel, n_l_blocks=nl)
    out = pl.pallas_call(
        kernel,
        grid=(nf, nl),
        in_specs=[
            pl.BlockSpec((bf, bl), lambda fi, li: (fi, li)),
            pl.BlockSpec((1, bl), lambda fi, li: (0, li)),
        ],
        out_specs=pl.BlockSpec((bf, 1), lambda fi, li: (fi, 0)),
        out_shape=jax.ShapeDtypeStruct((F, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bf, 1), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(adj.astype(jnp.int8), vals2)
    return out[:, 0]
