"""RG-LRU diagonal affine scan h_t = a_t*h_{t-1} + b_t — Pallas TPU kernel.

Grid: (batch, channel_blocks, chunks); the per-channel state (BR,) lives
in VMEM scratch across chunks so the only HBM traffic is the a/b chunk
stream — a single fused pass instead of the (read a, read b, write h)
triple of the unfused elementwise chain. Channel blocks are independent
(diagonal recurrence) => fully parallel over the second grid dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, h0_ref, h_ref, hlast_ref, h_scr, *,
            chunk: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = h0_ref[0, :].astype(jnp.float32)

    def step(t, _):
        a = a_ref[0, t, :].astype(jnp.float32)
        b = b_ref[0, t, :].astype(jnp.float32)
        h = a * h_scr[...] + b
        h_scr[...] = h
        h_ref[0, t, :] = h.astype(h_ref.dtype)
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(ic == nc - 1)
    def _emit():
        hlast_ref[0, :] = h_scr[...].astype(hlast_ref.dtype)


def rglru_scan_kernel(a, b, h0, *, chunk: int = 256, block_r: int = 512,
                      interpret: bool = False):
    """a, b: (B, S, R); h0: (B, R) f32. Returns (hs: (B,S,R), h_last)."""
    B, S, R = a.shape
    br = min(block_r, R)
    nc = S // chunk
    nr = R // br
    kernel = functools.partial(_kernel, chunk=chunk, nc=nc)
    seq_spec = pl.BlockSpec((1, chunk, br), lambda bi, ri, ci: (bi, ci, ri))
    vec_spec = pl.BlockSpec((1, br), lambda bi, ri, ci: (bi, ri))
    return pl.pallas_call(
        kernel,
        grid=(B, nr, nc),
        in_specs=[seq_spec, seq_spec, vec_spec],
        out_specs=[seq_spec, vec_spec],
        out_shape=[jax.ShapeDtypeStruct((B, S, R), a.dtype),
                   jax.ShapeDtypeStruct((B, R), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((br,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
