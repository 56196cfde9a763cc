"""Public wrapper: pads the candidate axis to block multiples and the
training axis to sublane multiples (masked points contribute 0), picks the
Pallas kernel on TPU and the jnp reference elsewhere (interpret mode is
available for kernel-correctness tests but is too slow for benchmarks)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.matern_score.kernel import matern_score_kernel
from repro.kernels.matern_score.ref import matern_score_ref


@partial(jax.jit, static_argnames=("block_n", "interpret", "use_ref"))
def matern_score(cand, x, alpha, mask, ls, sv, *, block_n: int = 128,
                 interpret: bool | None = None,
                 use_ref: bool | None = None):
    """Batched masked Matérn-5/2 posterior-mean scores (standardized).

    cand (S,N,d), x (S,n,d), alpha (S,n), mask (S,n), ls (S,), sv (S,)
    -> (S,N).
    """
    if use_ref is None:
        use_ref = jax.default_backend() != "tpu" and not interpret
    if use_ref:
        return matern_score_ref(cand, x, alpha, mask, ls, sv)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    S, N, d = cand.shape
    n = x.shape[1]
    bn = min(block_n, max(8, N))
    pn = (-N) % bn
    pm = (-n) % 8
    f32 = jnp.float32
    # candidates on the lane axis; per-scenario vectors as (S, 1, n)
    # rows and scalars as (S, 1, 1) — see kernel.py for the tiling
    cand_t = jnp.pad(cand.astype(f32), ((0, 0), (0, pn), (0, 0)))
    cand_t = jnp.swapaxes(cand_t, 1, 2)
    x = jnp.pad(x.astype(f32), ((0, 0), (0, pm), (0, 0)))
    alpha = jnp.pad(alpha.astype(f32), ((0, 0), (0, pm)))[:, None, :]
    mask = jnp.pad(mask.astype(f32), ((0, 0), (0, pm)))[:, None, :]
    out = matern_score_kernel(cand_t, x, alpha, mask,
                              ls.astype(f32).reshape(S, 1, 1),
                              sv.astype(f32).reshape(S, 1, 1),
                              block_n=bn, interpret=interpret)
    return out[:, 0, :N]
