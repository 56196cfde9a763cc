"""RWKV6 wkv recurrence — Pallas TPU kernel.

Grid: (batch, heads, num_chunks). Each grid step streams a (C, hd) chunk
of r/k/v/logw through VMEM and walks it sequentially with the (hd, hd)
fp32 state resident in VMEM scratch — the HBM traffic per step is the
chunk itself, not the state, which is the whole point: the state
(hd^2 = 160^2 fp32 = 102 KB) never round-trips to HBM between tokens.

Exact (no chunked-matmul exp-factorization; DESIGN.md notes the overflow
hazard of that variant) — matches the sequential-scan oracle bit-for-bit
in fp32 up to reassociation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, s0_ref, o_ref, sout_ref,
            s_scr, *, chunk: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    u = u_ref[0, :].astype(jnp.float32)                    # (hd,)

    def step(t, _):
        rt = r_ref[0, t, 0, :].astype(jnp.float32)         # (hd,)
        kt = k_ref[0, t, 0, :].astype(jnp.float32)
        vt = v_ref[0, t, 0, :].astype(jnp.float32)
        lwt = lw_ref[0, t, 0, :].astype(jnp.float32)
        s = s_scr[...]                                     # (hd_k, hd_v)
        # o_t = r_t @ (S + diag(u) k_t v_t^T) = r@S + (r·(u*k)) v
        o = jax.lax.dot_general(rt, s, (((0,), (0,)), ((), ()))) \
            + jnp.sum(rt * u * kt) * vt
        o_ref[0, t, 0, :] = o.astype(o_ref.dtype)
        s_scr[...] = jnp.exp(lwt)[:, None] * s + kt[:, None] * vt[None, :]
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)

    @pl.when(ic == nc - 1)
    def _emit():
        sout_ref[0, 0] = s_scr[...].astype(sout_ref.dtype)


def rwkv6_scan_kernel(r, k, v, logw, u, s0, *, chunk: int = 128,
                      interpret: bool = False):
    """r,k,v,logw: (B,S,H,hd); u: (H,hd); s0: (B,H,hd,hd) f32.
    Returns (o: (B,S,H,hd), s_last: (B,H,hd,hd))."""
    B, S, H, hd = r.shape
    nc = S // chunk
    kernel = functools.partial(_kernel, chunk=chunk, nc=nc)
    seq_spec = pl.BlockSpec((1, chunk, 1, hd), lambda b, h, c: (b, c, h, 0))
    state_spec = pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, hd), lambda b, h, c: (h, 0)),
                  state_spec],
        out_specs=[seq_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((B, S, H, hd), r.dtype),
                   jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, logw, u, s0)
