"""Fused batched Matérn-5/2 scoring — Pallas TPU kernel.

Grid: (scenario, candidate_blocks). Each program instance loads one
``(d, block_n)`` candidate tile plus its scenario's full ``(n, d)``
training set, builds the masked Matérn-5/2 cross-kernel tile in VMEM and
immediately contracts it with the scenario's ``alpha`` vector — the
``(n, block_n)`` tile never leaves VMEM, so the only HBM traffic is the
candidate stream in and the ``(1, block_n)`` scores out.

Layout: the candidate axis is the lane (last) axis of every tile, so the
cross-kernel tile is ``(n, block_n)`` — training points on sublanes,
candidates on lanes — and the contraction with ``alpha`` is a
``(1, n) @ (n, block_n)`` MXU dot that lands directly in a lane-dense
``(1, block_n)`` output row. Per-scenario vectors are passed as
``(S, 1, n)`` rows and the scalars ``ls``/``sv`` as ``(S, 1, 1)``, so
every block's last two dims
are either full array dims or multiples of the ``(8, 128)`` tiling that
Mosaic requires. The trailing input dim d (=2 for this problem) is
unrolled: distances are VPU broadcasts, not an MXU contraction.

CPU/GPU fall back to interpret mode or the jnp reference (see ``ops.py``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SQRT5 = 2.23606797749979


def _kernel(cand_ref, x_ref, alpha_ref, mask_ref, ls_ref, sv_ref, out_ref):
    c = cand_ref[0].astype(jnp.float32)          # (d, bn)
    x = x_ref[0].astype(jnp.float32)             # (n, d)
    alpha = alpha_ref[0].astype(jnp.float32)     # (1, n)
    mask = mask_ref[0].astype(jnp.float32)       # (1, n)
    ls = ls_ref[0]                               # (1, 1)
    sv = sv_ref[0]                               # (1, 1)

    d2 = jnp.zeros((x.shape[0], c.shape[1]), jnp.float32)
    for j in range(c.shape[0]):                  # d is tiny and static
        d2 = d2 + jnp.square(x[:, j:j + 1] - c[j:j + 1, :])
    r = jnp.sqrt(jnp.maximum(d2, 1e-16)) / ls
    k = sv * (1.0 + SQRT5 * r + 5.0 * r * r / 3.0) * jnp.exp(-SQRT5 * r)
    w = alpha * mask                             # (1, n)
    # full f32: at the default precision the MXU rounds both operands to
    # bf16, which put v5e scores up to 4e-2 off a float64 reference
    out_ref[0] = jnp.dot(w, k, precision=jax.lax.Precision.HIGHEST,
                         preferred_element_type=jnp.float32
                         ).astype(out_ref.dtype)


def matern_score_kernel(cand_t, x, alpha, mask, ls, sv, *, block_n: int = 128,
                        interpret: bool = False):
    """cand_t (S,d,N), x (S,n,d), alpha (S,1,n), mask (S,1,n) f32,
    ls/sv (S,1,1) -> (S,1,N). N must be a multiple of block_n (ops.py
    pads); on TPU block_n is a multiple of 128 or equal to N."""
    S, d, N = cand_t.shape
    n = x.shape[1]
    nb = N // block_n
    return pl.pallas_call(
        _kernel,
        grid=(S, nb),
        in_specs=[
            pl.BlockSpec((1, d, block_n), lambda si, ni: (si, 0, ni)),
            pl.BlockSpec((1, n, d), lambda si, ni: (si, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda si, ni: (si, 0, 0)),
            pl.BlockSpec((1, 1, n), lambda si, ni: (si, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda si, ni: (si, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda si, ni: (si, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_n), lambda si, ni: (si, 0, ni)),
        out_shape=jax.ShapeDtypeStruct((S, 1, N), jnp.float32),
        name="matern_score",
        interpret=interpret,
    )(cand_t, x, alpha, mask, ls, sv)
