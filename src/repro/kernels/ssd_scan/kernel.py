"""Mamba-2 SSD chunk scan as a Pallas TPU kernel.

TPU-native mapping of the SSD (state-space duality) algorithm
[arXiv:2405.21060]:
  * grid (B, H, NC) with the chunk axis innermost (*arbitrary* semantics):
    the inter-chunk state (P, N) f32 is carried in VMEM scratch — the
    sequential recurrence never leaves the chip;
  * intra-chunk work is three MXU matmuls per chunk: CB^T (Q x Q), the
    masked-decay attention-like product with x (Q x P), and the state
    outer products (exactly the "dual" quadratic form of SSD);
  * chunk length Q defaults to 256 and P, N are 64/128 — all MXU-aligned;
    VMEM per step ~ Q*(P+2N)*4B + Q^2*4B ≈ 0.6 MB at Q=256.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _ssd_kernel(x_ref, dtr_ref, dtc_ref, a_ref, b_ref, c_ref, y_ref, h_ref,
                *, chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0, 0].astype(jnp.float32)                # (Q, P)
    dt_r = dtr_ref[0, 0]                               # (1, Q) row
    dt_c = dtc_ref[0, 0]                               # (Q, 1) column
    a = a_ref[pl.program_id(1)]                        # scalar A_h < 0
    B = b_ref[0, 0].astype(jnp.float32)                # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)                # (Q, N)

    # cumulative decay in both orientations as masked lane/sublane sums
    # (no in-kernel cumsum or transpose): cum_i = sum_{j <= i} dt_j * a
    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    adt_r = dt_r * a
    adt_c = dt_c * a
    cum_c = jnp.sum(jnp.where(jq <= iq, adt_r, 0.0), axis=1,
                    keepdims=True)                     # (Q, 1)
    cum_r = jnp.sum(jnp.where(iq <= jq, adt_c, 0.0), axis=0,
                    keepdims=True)                     # (1, Q)
    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for i >= j
    L = jnp.where(iq >= jq, jnp.exp(cum_c - cum_r), 0.0)
    CB = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    M = CB * L * dt_r
    y_intra = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    # inter-chunk: y += (C * exp(cum)) @ h_prev^T     h: (P, N)
    Cdec = C * jnp.exp(cum_c)
    y_inter = jax.lax.dot_general(Cdec, h_ref[...],
                                  (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)
    # state update: h = exp(cum_last) * h + sum_q decay_q dt_q x_q B_q^T
    last = jnp.sum(adt_r, axis=1, keepdims=True)       # (1, 1)
    decay = jnp.exp(last - cum_c) * dt_c               # (Q, 1)
    hS = jax.lax.dot_general(x, B * decay, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (P, N)
    h_ref[...] = jnp.exp(last) * h_ref[...] + hS


def ssd_scan(xh, dt, A, Bh, Ch, chunk: int = 256, *,
             interpret: bool = False):
    """xh: (B,S,H,P); dt: (B,S,H) f32; A: (H,); Bh/Ch: (B,S,H,N).

    Returns y: (B,S,H,P).  S must be a multiple of `chunk` (callers pad
    with dt=0 — identity transition — as models/mamba2.py does).
    """
    b, s, h, p = xh.shape
    n = Bh.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    # head-major layout: every block's last two dims are (chunk, width)
    # or (1, chunk), which tile as the TPU requires; dt rides as both a
    # row and a column so the kernel never transposes it
    dt_hs = dt.astype(jnp.float32).transpose(0, 2, 1)          # (B, H, S)
    seq = lambda w: pl.BlockSpec((1, 1, chunk, w),
                                 lambda bi, hi, ci: (bi, hi, ci, 0))
    y = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            seq(p),
            pl.BlockSpec((1, 1, 1, chunk),
                         lambda bi, hi, ci: (bi, hi, 0, ci)),
            seq(1),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            seq(n),
            seq(n),
        ],
        out_specs=seq(p),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), xh.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(xh.transpose(0, 2, 1, 3), dt_hs[:, :, None, :], dt_hs[..., None],
      A.astype(jnp.float32), Bh.transpose(0, 2, 1, 3),
      Ch.transpose(0, 2, 1, 3))
    return y.transpose(0, 2, 1, 3)
