"""Flash attention (causal, grouped GQA) as a Pallas TPU kernel.

TPU-native design (not a CUDA port — DESIGN.md §2):
  * grid (B, G, NQ, NK) with the KV axis innermost and *arbitrary*
    dimension semantics: the online-softmax state (m, l, acc) lives in
    VMEM scratch and is carried across NK grid steps;
  * q is laid out head-major as (B, G, Sq*R, hd), so a q block is a
    (bq*R, hd) tile and the MXU sees a (bq*R, hd) x (hd, bk) matmul — R
    query heads per KV group ride along the sublane dim for free;
  * fully-masked causal blocks are skipped with @pl.when (real FLOP
    savings on TPU — the XLA fallback in models/layers.py can only mask);
  * block sizes default to 128/128: MXU-aligned (multiples of 128) and
    small enough that q, k, v, acc tiles fit VMEM comfortably
    (~(bq*R + 2*bk + bq*R)*hd*4B ≈ 5 MB at R=8, hd=128).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, r: int, causal: bool, scale: float,
                  n_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal: skip blocks strictly above the diagonal
    run = (not causal) or (ki * bk < (qi + 1) * bq)

    @pl.when(run)
    def _body():
        qf = q_ref[0, 0] * scale                       # (bq*R, hd)
        k = k_ref[0, 0]                                # (bk, hd)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(qf, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            # row i is query position qi*bq + i // R; "kpos <= qpos" is
            # tested as (kpos - qi*bq) * R <= i, which needs no division
            row = jax.lax.broadcasted_iota(jnp.int32, (bq * r, bk), 0)
            kd = ki * bk - qi * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq * r, bk), 1)
            s = jnp.where(kd * r <= row, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, bq: int = 128,
                        bk: int = 128, interpret: bool = False):
    """q: (B, Sq, G, R, hd); k, v: (B, Sk, G, hd) -> (B, Sq, G, R, hd)."""
    b, sq, g, r, hd = q.shape
    sk = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    nq, nk = sq // bq, sk // bk
    scale = 1.0 / math.sqrt(hd)
    kernel = functools.partial(_flash_kernel, bq=bq, bk=bk, r=r,
                               causal=causal, scale=scale, n_k_blocks=nk)
    # head-major layout: every block's last two dims are (rows, hd), so
    # they tile as the TPU requires (rows a multiple of 8, hd the full dim)
    qg = q.transpose(0, 2, 1, 3, 4).reshape(b, g, sq * r, hd)
    kg = k.transpose(0, 2, 1, 3)
    vg = v.transpose(0, 2, 1, 3)
    out = pl.pallas_call(
        kernel,
        grid=(b, g, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq * r, hd),
                         lambda bi, gi, qi, ki: (bi, gi, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bi, gi, qi, ki: (bi, gi, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bi, gi, qi, ki: (bi, gi, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq * r, hd),
                               lambda bi, gi, qi, ki: (bi, gi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, g, sq * r, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq * r, 1), jnp.float32),    # m
            pltpu.VMEM((bq * r, 1), jnp.float32),    # l
            pltpu.VMEM((bq * r, hd), jnp.float32),   # acc
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(qg, kg, vg)
    return out.reshape(b, g, sq, r, hd).transpose(0, 2, 1, 3, 4)
