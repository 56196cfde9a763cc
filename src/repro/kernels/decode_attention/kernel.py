"""Flash-decoding: one query token vs a long KV cache — Pallas TPU kernel.

Grid: (batch, q_heads, num_kv_blocks); online-softmax state in VMEM
scratch across kv blocks. The cache may be a ring buffer: masking is
driven by the kv_pos array (INT32_MAX marks empty slots), not by block
indices. The per-step working set is (BK, hd) K/V tiles + (hd,) fp32
accumulators, so arbitrarily long caches stream through VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qpos_ref, q_ref, k_ref, v_ref, kpos_ref, o_ref,
            m_scr, l_scr, acc_scr, *, scale: float, window: int, nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0, :].astype(jnp.float32)                # (hd,)
    k = k_ref[0, :, 0, :].astype(jnp.float32)             # (BK, hd)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    kpos = kpos_ref[0, :]                                  # (BK,) int32
    qpos = qpos_ref[0]

    s = jax.lax.dot_general(k, q, (((1,), (0,)), ((), ()))) * scale  # (BK,)
    mask = kpos <= qpos
    if window:
        mask = jnp.logical_and(mask, qpos - kpos < window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[0]
    m_new = jnp.maximum(m_prev, s.max())
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[0] = l_scr[0] * corr + p.sum()
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p, v, (((0,), (0,)), ((), ())))
    m_scr[0] = m_new

    @pl.when(ik == nk - 1)
    def _emit():
        l = l_scr[0]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, :] = (acc_scr[...] / l).astype(o_ref.dtype)


def decode_attention_kernel(q, k, v, kv_pos, q_pos, *, window: int = 0,
                            bk: int = 512, interpret: bool = False):
    """q: (B, Hq, hd); k/v: (B, T, Hkv, hd); kv_pos: (B, T); q_pos: (B,)."""
    B, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    group = Hq // Hkv
    nk = T // bk
    scale = 1.0 / (hd ** 0.5)

    kernel = functools.partial(_kernel, scale=scale, window=window, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, Hq, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda b, h, j: (b,)),
            pl.BlockSpec((1, 1, hd), lambda b, h, j: (b, h, 0)),
            pl.BlockSpec((1, bk, 1, hd),
                         lambda b, h, j: (b, j, h // group, 0)),
            pl.BlockSpec((1, bk, 1, hd),
                         lambda b, h, j: (b, j, h // group, 0)),
            pl.BlockSpec((1, bk), lambda b, h, j: (b, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda b, h, j: (b, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((hd,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q_pos, q, k, v, kv_pos)
