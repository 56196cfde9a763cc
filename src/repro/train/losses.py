"""Vocab-parallel, sequence-chunked cross-entropy.

The (B,S,V) logits tensor is never materialized: the unembed stays
vocab-sharded on the `model` axis, each shard computes its local logits
one sequence-chunk at a time, and log-sum-exp terms combine with
pmax/psum — the standard Megatron vocab-parallel CE, here via shard_map.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS



def _chunked_ce_dense(hidden, w, labels, n_chunks: int, vocab_valid: int):
    """Single-shard path: chunk over flattened tokens."""
    B, S, D = hidden.shape
    T = B * S
    h = hidden.reshape(T, D)
    lab = labels.reshape(T)
    cs = -(-T // n_chunks)
    pad = cs * n_chunks - T
    h = jnp.pad(h, ((0, pad), (0, 0)))
    lab = jnp.pad(lab, (0, pad))
    valid = jnp.pad(jnp.ones((T,), jnp.float32), (0, pad))

    def chunk(carry, xs):
        hc, lc, vc = xs
        logits = (hc @ w).astype(jnp.float32)
        # padded vocab tail must not contribute
        vmask = jnp.arange(logits.shape[-1]) < vocab_valid
        logits = jnp.where(vmask, logits, -1e30)
        lz = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, lc[:, None], axis=-1)[:, 0]
        nll = (lz - ll) * vc
        zsq = jnp.square(lz) * vc
        return (carry[0] + nll.sum(), carry[1] + zsq.sum()), None

    (nll, zsq), _ = jax.lax.scan(
        chunk, (jnp.zeros(()), jnp.zeros(())),
        (h.reshape(n_chunks, cs, D), lab.reshape(n_chunks, cs),
         valid.reshape(n_chunks, cs)))
    return nll / T, zsq / T


def vocab_parallel_ce(hidden, unembed_w, labels, cfg, ctx,
                      n_chunks: int = 8, z_loss: float = 0.0):
    """Mean next-token NLL (+ optional z-loss). hidden: (B,S,D);
    unembed_w: (D, Vp) vocab-sharded; labels: (B,S) int32 < vocab_size."""
    vocab_valid = cfg.vocab_size

    if (ctx is None or ctx.rules.get("vocab") != "model"
            or ctx.axis_sizes.get("model", 1) <= 1):
        nll, zsq = _chunked_ce_dense(hidden.astype(jnp.float32), unembed_w,
                                     labels, n_chunks, vocab_valid)
        return nll + z_loss * zsq

    mesh = ctx.mesh
    batch_axes = ctx.rules.get("batch")
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    elif batch_axes is None:
        batch_axes = ()

    def f(h, w, lab):
        Bl, S, D = h.shape
        T = Bl * S
        hf = h.reshape(T, D)
        lf = lab.reshape(T)
        cs = -(-T // n_chunks)
        pad = cs * n_chunks - T
        hf = jnp.pad(hf, ((0, pad), (0, 0)))
        lf = jnp.pad(lf, (0, pad))
        valid = jnp.pad(jnp.ones((T,), jnp.float32), (0, pad))

        vloc = w.shape[1]
        lo = jax.lax.axis_index("model") * vloc

        def chunk(carry, xs):
            hc, lc, vc = xs
            logits = (hc @ w).astype(jnp.float32)        # (cs, vloc)
            col = lo + jnp.arange(vloc)
            logits = jnp.where(col < vocab_valid, logits, -1e30)
            # max-shift is gradient-free (cancels in d/dlogits of LSE);
            # pmax has no JVP rule, so feed it a stopped gradient — exact
            m = jax.lax.pmax(
                jax.lax.stop_gradient(logits.max(axis=-1)), "model")
            denom = jax.lax.psum(
                jnp.exp(logits - m[:, None]).sum(axis=-1), "model")
            loc = lc - lo
            ok = (loc >= 0) & (loc < vloc)
            ll = jnp.where(
                ok, jnp.take_along_axis(
                    logits, jnp.clip(loc, 0, vloc - 1)[:, None], axis=-1)[:, 0],
                0.0)
            ll = jax.lax.psum(ll, "model")
            lz = m + jnp.log(denom)
            nll = (lz - ll) * vc
            zsq = jnp.square(lz) * vc
            return (carry[0] + nll.sum(), carry[1] + zsq.sum()), None

        (nll, zsq), _ = jax.lax.scan(
            chunk, (jnp.zeros(()), jnp.zeros(())),
            (hf.reshape(n_chunks, cs, D), lf.reshape(n_chunks, cs),
             valid.reshape(n_chunks, cs)))
        loss = nll / T + z_loss * zsq / T
        for ax in batch_axes:
            loss = jax.lax.pmean(loss, ax)
        return loss

    # tokens must be REPLICATED over `model` inside the CE shard_map (the
    # pmax/psum combine is over vocab shards of the SAME tokens). Under
    # sequence parallelism jit inserts the trunk->loss all-gather here.
    ba = ctx.rules.get("batch")
    return jax.shard_map(
        f, mesh=mesh,
        in_specs=(PS(ba, None, None),
                  PS(None, ctx.rules.get("vocab")),
                  PS(ba, None)),
        out_specs=PS(),
        check_vma=False,
    )(hidden, unembed_w, labels)
