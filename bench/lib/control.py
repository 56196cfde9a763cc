"""The lower-precision controls of the ``correct`` comparison, and the
planted fault of the loop body's acquisition.

The configurations state float32 on the device. Each control puts
bfloat16 arithmetic into one layer of the timed path:

* ``oracle``: the reference's utility oracle computed in bfloat16, in
  the place of the planner's device oracle (``jax_cost.utility``, which
  every init-design and loop evaluation calls), so that each evaluation
  the server makes and each answer it reports carries bfloat16 values.
* ``acquisition``: the GP posterior that the refit hands the acquisition
  (hyperparameters, Cholesky factor, weights, data) and the candidates,
  rounded to bfloat16, and the acquisition's chosen point rounded to
  bfloat16: the GP refit and acquisition kept in bfloat16.

The fault ``random_acquisition`` makes the acquisition pick a
pseudo-random candidate of its block, in range and evaluated correctly,
in place of the best-scoring one.

A comparison that cannot tell these from the planner is too loose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib import reference as ref


def bf16_utility(params, li, p):
    """(utility, accuracy, feasible) of the reference oracle, in bfloat16,
    with the device oracle's signature."""
    bf = jnp.bfloat16

    def c(x):
        return jnp.asarray(x).astype(bf)

    p = c(p)
    snr = p * c(params["gain_lin"]) / c(params["noise_w"])
    rate = c(params["bandwidth_hz"]) * jnp.log2(c(1.0) + snr)
    tx = c(params["tx_bits"])[li] / jnp.maximum(rate, c(1e-30))
    e = c(params["dev_energy"])[li] + p * tx
    t = c(params["dev_delay"])[li] + tx + c(params["srv_delay"])[li]
    e_max, tau = c(params["e_max"]), c(params["tau_max"])
    base = c(params["base_acc"])
    q = c(ref.QUANTUM)
    lf = li.astype(bf)
    raw = base + c(params["bump"]) * jnp.exp(
        c(-0.5) * jnp.square((lf - c(params["peak_layer"]))
                             / c(params["sigma_u"])))
    full = raw - c(ref.EPS_ENERGY) * e / e_max
    dead = (e > e_max) | (t > tau / c(ref.COMPLETION_FLOOR))
    feas = (e <= e_max) & (t <= tau)
    u = jnp.where(dead, c(0.0), jnp.where(feas, full, base))
    acc_full = jnp.floor(raw / q) * q
    acc = jnp.where(dead, c(0.0),
                    jnp.where(feas, acc_full, jnp.floor(base / q) * q))
    return u.astype(jnp.float32), acc.astype(jnp.float32), feas


def _bf16(x):
    x = jnp.asarray(x)
    if jnp.issubdtype(x.dtype, jnp.floating):
        return x.astype(jnp.bfloat16).astype(x.dtype)
    return x


def bf16_acquisition(orig):
    """``_maximize_core`` on a bfloat16 posterior and candidate block,
    returning a bfloat16 point."""
    def maximize(gp, params, cand, best_feasible, *args, **kw):
        a, s, g = orig(jax.tree.map(_bf16, gp), params, _bf16(cand),
                       _bf16(best_feasible), *args, **kw)
        return _bf16(a), s, g
    return maximize


def random_acquisition(gp, params, cand, best_feasible, lam_base, *args,
                       **kw):
    """A pseudo-random candidate of the block: a hash of each candidate
    with the lane's incumbent, schedule weight and GP scale, so that the
    pick moves from one iteration to the next."""
    h = jnp.sin(cand @ jnp.array([12.9898, 78.233]) + 1e3 * lam_base
                + 7.0 * best_feasible + 3.0 * gp["y_sigma"]) * 43758.5453
    a = cand[jnp.argmax(h - jnp.floor(h))]
    return a, jnp.float32(0.0), None


KINDS = ("oracle", "acquisition", "random_acquisition")


def install(kind: str = "oracle"):
    """Put the control or fault ``kind`` in the planner's place and drop
    every compiled program, so the server traces it anew. Returns the
    undo."""
    from repro.core import jax_cost, wholerun
    if kind == "oracle":
        mod, name, new = jax_cost, "utility", bf16_utility
    elif kind == "acquisition":
        mod, name = wholerun, "_maximize_core"
        new = bf16_acquisition(wholerun._maximize_core)
    elif kind == "random_acquisition":
        mod, name, new = wholerun, "_maximize_core", random_acquisition
    else:
        raise ValueError(f"unknown control {kind!r}")
    orig = getattr(mod, name)
    setattr(mod, name, new)
    jax.clear_caches()

    def undo():
        setattr(mod, name, orig)
        jax.clear_caches()
    return undo
