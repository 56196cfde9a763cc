"""Causal GQA flash attention — Pallas TPU kernel.

Grid: (batch, q_heads, num_q_blocks, num_kv_blocks); the kv dimension is
innermost with "arbitrary" semantics so the online-softmax state lives in
VMEM scratch across kv steps. GQA is folded into the K/V BlockSpec index
maps (kv head = q head // group). Causal + sliding-window masking is
computed from block indices (positions are array-aligned for
training/prefill). Upper-triangle kv blocks are skipped with pl.when —
the causal-skip the pure-jnp path only gets after its §Perf iteration.

VMEM working set per grid step (bf16 in, f32 accum):
  q (BQ, hd) + k,v (BK, hd) + scratch m,l (BQ,) + acc (BQ, hd)
  = e.g. BQ=BK=512, hd=128: 0.92 MB — comfortably within a v5e core's
  ~16 MB VMEM with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, window: int, bq: int, bk: int, nk: int,
            causal: bool):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = iq * bq
    k_lo = ik * bk
    # causal skip: this kv block intersects the allowed region iff its
    # first row is <= the q block's last row (and within the window)
    needed = True
    if causal:
        needed = k_lo <= q_lo + bq - 1
    if window:
        needed = jnp.logical_and(needed, q_lo - (k_lo + bk - 1) < window)

    @pl.when(needed)
    def _step():
        q = q_ref[0, :, 0, :].astype(jnp.float32)          # (BQ, hd)
        k = k_ref[0, :, 0, :].astype(jnp.float32)          # (BK, hd)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale

        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos <= qpos if causal else jnp.full((bq, bk), True)
        if window:
            mask = jnp.logical_and(mask, qpos - kpos < window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _emit():
        l = l_scr[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, 0, :] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           bq: int = 512, bk: int = 512,
                           interpret: bool = False):
    """q: (B, Sq, Hq, hd); k/v: (B, Skv, Hkv, hd). Sq % bq == Skv % bk == 0."""
    B, Sq, Hq, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    nq, nk = Sq // bq, Skv // bk
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_kernel, scale=scale, window=window,
                               bq=bq, bk=bk, nk=nk, causal=causal)
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, 1, hd), lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec((1, bk, 1, hd),
                         lambda b, h, i, j: (b, j, h // group, 0)),
            pl.BlockSpec((1, bk, 1, hd),
                         lambda b, h, i, j: (b, j, h // group, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, hd), lambda b, h, i, j: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Sq, Hq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
