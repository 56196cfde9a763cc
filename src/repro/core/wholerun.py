"""Whole-run on-device Bayes-Split-Edge: Algorithm 1 as ONE dispatch.

``BatchedBayesSplitEdge`` (PR 1) made each BO iteration two device
dispatches but kept the Algorithm-1 bookkeeping — eval ledger, probe
queue, early-stop masking, feasible-only GP filtering — in host Python,
paying a host<->device round-trip per iteration plus numpy restacking.
This engine moves that bookkeeping into fixed-shape device arrays stepped
by a ``lax.while_loop``: an entire S-scenario BO run (init design + all
<=20 iterations) is a single jitted program launch.

Each loop step performs exactly one evaluation per live scenario —
either the front of its discrete-probe queue (Alg. 1 mixed-integer local
search) or the acquisition argmax — so every scenario's eval sequence is
identical to the host engines'; the host-driven paths remain the
trace-equivalence oracle (``tests/test_wholerun.py``).

Inside the loop, GP refits are warm-started from the previous
iteration's hyperparameters with an adaptive step count
(``gp._fit_core_from``): Adam stops once the MLL gradient norm falls
below ``GPConfig.warm_gtol``, cutting the ~150-step from-scratch refit
cost ~5x. Warm starting changes the fit trajectory, so it is gated by an
equivalence-tolerance study (incumbent-trace divergence bounds as tests)
and ``warm_start=False`` falls back to bitwise cold-fit behavior.

The leading scenario axis is embarrassingly parallel:
``run(...)`` with a mesh shards it via ``shard_map`` over a 1-D
``("scen",)`` mesh — each device steps its own ``while_loop`` over its
shard with zero collectives, and results gather host-side.

The scenario axis is architecture-heterogeneous: per-layer constraint
surfaces and the boundary candidate block are padded to the batch-wide
``L_max`` (``cfg.l_pad``) with masked tails, and every layer clip inside
the loop uses the scenario's own ``params["n_layers"]``, so one compiled
whole-run program mixes VGG19 and ResNet101 scenarios while padded tail
split points stay unreachable. A single-architecture batch pads to its
own ``L`` — the bit-identical historical layout.

Heterogeneous-*budget* batches add a second waste axis: early-stopped
scenarios stay as frozen-yet-computed lanes inside the ``while_loop``.
With ``compact=True`` (the default off-mesh) the run becomes a short
host-driven sequence of phase dispatches over the same loop body: each
phase's ``while_loop`` additionally exits once the live-lane count falls
to half the lane capacity, the driver gathers the surviving lanes into a
dense prefix (an on-device permutation of the full state pytree — GP
datasets, ledger, probe queue, warm-start thetas) and re-dispatches the
next phase at the next power-of-2 lane count; retired lanes' results are
inverse-scattered back into the original scenario order. Every lane's
trajectory is a function of its own state only (the established
sharding-invariance argument), so compaction is a pure re-scheduling:
cold runs are bitwise identical to the uncompacted program, warm runs
stay within the studied trace tolerance (``tests/test_compaction.py``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from repro.core import gp as gpm
from repro.core import jax_cost as jc
from repro.core import surrogate as smod
from repro.core.acquisition import (REFINE_LR, REFINE_STEPS, AcqWeights,
                                    _maximize_core, assemble_candidates_dev,
                                    candidate_grid)
from repro.core.batch_bo import Scenario
from repro.core.bo import BOResult, _init_grid
from repro.core.engine_config import EngineConfig, resolve_config
from repro.core.priorbank import _THETA_KEYS, PriorBank, stage_prior


@dataclasses.dataclass(frozen=True)
class WholeRunConfig:
    """Static (trace-time) shape/flag configuration of the device program."""
    n_init: int
    n_max_repeat: int
    budget_max: int              # eval-ledger length (max budget in batch)
    l_pad: int                   # batch-wide padded layer count (L_max);
                                 # per-scenario clips use params["n_layers"]
    constraint_aware: bool
    gp_feasible_only: bool
    use_schedules: bool
    warm_start: bool
    gp: gpm.GPConfig
    # divergence quarantine (streaming fault tolerance): lanes with
    # non-finite GP *data* always fault (impossible in healthy runs —
    # evals are finite — so the default detector keeps every healthy
    # program bitwise-identical); with fault_on_divergence the detector
    # additionally faults lanes whose refit carry / chosen point went
    # non-finite. Strict mode changes behavior on workloads where a
    # warm refit diverges organically (historically survivable
    # deterministic garbage), so it is opt-in.
    fault_on_divergence: bool = False
    # pluggable surrogate (None -> the exact GP, bitwise-historical) and
    # the transfer-learned prior plumbing: with use_prior the per-lane
    # (prior_mu, prior_n0) state feeds the fit's mean-prior shrinkage and
    # bank-hit lanes enter seeded with their banked theta. Both are
    # static: a frozen-dataclass surrogate keeps the config hashable
    surrogate: Optional[smod.Surrogate] = None
    use_prior: bool = False


def _sched(w0, wT, t):
    """Device mirror of acquisition.schedule: w0 * (wT/w0)^t, 0 if w0<=0."""
    safe = jnp.where(w0 > 0.0, w0, 1.0)
    return jnp.where(w0 > 0.0, w0 * (wT / safe) ** t, 0.0)


def _sel(pred, new, old):
    """Per-scenario select with broadcasting over trailing dims."""
    p = pred.reshape(pred.shape + (1,) * (new.ndim - pred.ndim))
    return jnp.where(p, new, old)


def _next_pow2(n: int) -> int:
    s = 1
    while s < n:
        s *= 2
    return s


def _init_state(s: int, cfg: WholeRunConfig, dim: int = 2):
    m, t = cfg.gp.max_points, cfg.budget_max
    q = t + 2                    # probe queue can never outgrow the budget
    f32, i32 = jnp.float32, jnp.int32
    th0 = smod.resolve(cfg.surrogate, cfg.gp).init_theta()
    return dict(
        # GP dataset (feasible-only gated numpy mirror of ScenarioState)
        x=jnp.zeros((s, m, dim), f32), y=jnp.zeros((s, m), f32),
        mask=jnp.zeros((s, m), bool), n_pts=jnp.zeros((s,), i32),
        # eval ledger
        ev_u=jnp.zeros((s, t), f32), ev_acc=jnp.zeros((s, t), f32),
        ev_feas=jnp.zeros((s, t), bool), ev_trace=jnp.zeros((s, t), f32),
        ev_l=jnp.full((s, t), -1, i32), ev_pr=jnp.zeros((s, t), f32),
        n=jnp.zeros((s,), i32),
        # incumbent
        best_a=jnp.zeros((s, dim), f32),
        best_u=jnp.full((s,), -jnp.inf, f32),
        has_best=jnp.zeros((s,), bool),
        inc_layer=jnp.full((s,), -1, i32),
        # discrete-probe queue (Alg. 1 mixed-integer local search)
        probe_q=jnp.zeros((s, q, dim), f32),
        probe_n=jnp.zeros((s,), i32),
        # early-stop masking
        n_c=jnp.zeros((s,), i32), active=jnp.ones((s,), bool),
        # streaming admission bookkeeping: `seeded` is the per-lane
        # cold-seed flag for the warm-start carry (False until the lane's
        # first post-init body iteration — the per-lane generalization of
        # the old global iteration-0 flag), `gen` the lane generation
        # counter bumped by every admission scatter so a re-admitted
        # lane's rows are auditable against its previous occupant's
        seeded=jnp.zeros((s,), bool), gen=jnp.zeros((s,), i32),
        # divergence quarantine: raised by the loop body when a lane's
        # refit or acquisition goes non-finite — the lane freezes (so the
        # phase exits on the retirement event) instead of poisoning the
        # batch; the streaming driver then escalates (re-seed -> scrub ->
        # degraded retirement) host-side
        fault=jnp.zeros((s,), bool),
        # warm-start carry + fit-cost accounting
        theta=jax.tree.map(lambda v: jnp.broadcast_to(v, (s,)).astype(f32),
                           th0),
        fit_steps=jnp.zeros((s,), i32), fit_calls=jnp.zeros((s,), i32),
        # transfer-learned mean prior (per-lane): n0 pseudo-observations
        # at mu0 shrink the fit's target centering (gp._standardize).
        # Zeros — the default, and every bank miss — reproduce the
        # prior-free arithmetic bitwise; the arrays ride the compaction
        # gathers / admission scatters / checkpoints like any lane state
        prior_mu=jnp.zeros((s,), f32), prior_n0=jnp.zeros((s,), f32),
    )


# -- per-scenario Algorithm-1 bookkeeping (vmapped by the callers) ----------

def _observe(st, a, params, cfg: WholeRunConfig):
    """One oracle evaluation: ledger append, incumbent update, gated GP
    dataset append, seen-key record (mirror of ScenarioState.observe)."""
    li, p = jc.denormalize(params, a)
    u, acc, feas = jc.utility(params, li, p)
    n = st["n"]
    newbest = feas & (u > st["best_u"])
    best_u = jnp.where(newbest, u, st["best_u"])
    st = dict(st)
    st["best_u"] = best_u
    st["best_a"] = jnp.where(newbest, a, st["best_a"])
    st["has_best"] = st["has_best"] | newbest
    st["ev_u"] = st["ev_u"].at[n].set(u)
    st["ev_acc"] = st["ev_acc"].at[n].set(acc)
    st["ev_feas"] = st["ev_feas"].at[n].set(feas)
    st["ev_trace"] = st["ev_trace"].at[n].set(
        jnp.where(jnp.isfinite(best_u), best_u, 0.0))
    st["ev_l"] = st["ev_l"].at[n].set(li)
    st["ev_pr"] = st["ev_pr"].at[n].set(jc.seen_key(p))
    add = feas if cfg.gp_feasible_only else jnp.bool_(True)
    k = jnp.minimum(st["n_pts"], cfg.gp.max_points - 1)
    st["x"] = st["x"].at[k].set(jnp.where(add, a, st["x"][k]))
    st["y"] = st["y"].at[k].set(jnp.where(add, u, st["y"][k]))
    st["mask"] = st["mask"].at[k].set(st["mask"][k] | add)
    st["n_pts"] = st["n_pts"] + (
        add & (st["n_pts"] < cfg.gp.max_points)).astype(jnp.int32)
    st["n"] = n + 1
    return st


def _push_probes(st, params, cfg: WholeRunConfig):
    """Queue +-1 layer neighbors of a new incumbent layer at the analytic
    min-feasible power (mirror of ScenarioState.push_probes)."""
    if not cfg.constraint_aware:
        return st
    l_star, p_star = jc.denormalize(params, st["best_a"])
    do = st["has_best"] & (l_star != st["inc_layer"])
    st = dict(st)
    st["inc_layer"] = jnp.where(do, l_star, st["inc_layer"])
    t = st["ev_l"].shape[0]
    q = st["probe_q"].shape[0]
    idx = jnp.arange(t)
    # the scenario's OWN layer count, not the batch-wide padded L_max:
    # a probe must never land on a padded tail split of a shorter arch
    l_hi = params["n_layers"].astype(jnp.int32)
    for dl in (1, -1):
        l = l_star + dl
        ok = do & (l >= 1) & (l <= l_hi)
        lc = jnp.clip(l, 1, l_hi)
        a = jc.project_feasible(params, jc.normalize(params, lc, p_star))
        lp, pp = jc.denormalize(params, a)
        seen = jnp.any((idx < st["n"]) & (st["ev_l"] == lp)
                       & (st["ev_pr"] == jc.seen_key(pp)))
        enq = ok & ~seen & (st["probe_n"] < q)
        qi = jnp.minimum(st["probe_n"], q - 1)
        st["probe_q"] = st["probe_q"].at[qi].set(
            jnp.where(enq, a, st["probe_q"][qi]))
        st["probe_n"] = st["probe_n"] + enq.astype(jnp.int32)
    return st


def _step(st, a, params, budget, cfg: WholeRunConfig):
    """Observation + probe push + incumbent-repeat early stop
    (Alg. 1 lines 14-21; mirror of ScenarioState.step)."""
    li_n, p_n = jc.denormalize(params, a)
    li_b, p_b = jc.denormalize(params, st["best_a"])
    same = st["has_best"] & (li_n == li_b) & (p_n == p_b)
    st = _observe(st, a, params, cfg)
    st = _push_probes(st, params, cfg)
    n_c = jnp.where(same, st["n_c"] + 1, 0)
    st["n_c"] = n_c
    st["active"] = (st["n"] < budget) & (n_c < cfg.n_max_repeat)
    return st


def _one_init(st, p1, pts, budget, cfg: WholeRunConfig):
    """The init design for one scenario (vmapped by the callers)."""
    for j in range(cfg.n_init):
        st = _observe(st, pts[j], p1, cfg)
    st = _push_probes(st, p1, cfg)
    st["active"] = st["n"] < budget
    return st


def _pen_static(params, grid, boundary):
    """Eq.-(11) penalties for the grid + boundary candidate slots depend
    only on the channel — computed once per run, not per iteration."""
    return jnp.concatenate([
        jax.vmap(lambda p1: jc.penalty(p1, grid))(params),
        jax.vmap(jc.penalty)(params, boundary),
    ], axis=1)                                   # (S, G + L)


# -- the whole-run program ---------------------------------------------------

_OUT_KEYS = ("ev_u", "ev_acc", "ev_feas", "ev_trace", "ev_l", "n",
             "best_a", "best_u", "has_best", "fit_steps", "fit_calls",
             "gen", "fault")


@jax.jit
def _pack_words(tree) -> jax.Array:
    """Every leaf of ``tree`` (32-bit or bool) as int32 words, bit for
    bit, in one buffer: one transfer to the host in place of one per
    leaf."""
    words = []
    for v in jax.tree.leaves(tree):
        if v.dtype == jnp.bool_:
            v = v.astype(jnp.int32)
        elif v.dtype != jnp.int32:
            v = jax.lax.bitcast_convert_type(v, jnp.int32)
        words.append(v.reshape(-1))
    return jnp.concatenate(words)


def fetch_out(state: dict, theta: bool = False, it=None) -> dict:
    """Everything a retirement flush reads, in one host fetch: the
    whole-width ``_OUT_KEYS`` arrays and ``active``, the warm-start
    ``theta`` leaves the prior bank records (``theta=True``) and the loop
    counter ``it`` (when given), packed on the device into one buffer
    (:func:`_pack_words`) and unpacked on the host. The pack is one
    program per pool width, whatever the number of retiring lanes."""
    tree = {k: state[k] for k in _OUT_KEYS + ("active",)}
    if theta:
        tree["theta"] = {k: state["theta"][k] for k in _THETA_KEYS}
    if it is not None:
        tree["it"] = it
    leaves, treedef = jax.tree.flatten(tree)
    words = np.asarray(_pack_words(tree))
    out, at = [], 0
    for v in leaves:
        w = words[at:at + v.size]
        at += v.size
        if v.dtype == jnp.bool_:
            w = w != 0
        elif v.dtype != jnp.int32:
            w = w.view(v.dtype)
        out.append(w.reshape(v.shape))
    return jax.tree.unflatten(treedef, out)


def take_rows(snap: dict, rows: Sequence[int]) -> dict:
    """Rows ``rows`` of a :func:`fetch_out` snapshot: the ``_OUT_KEYS``
    (and ``theta``, if fetched), bitwise the device gather
    ``state[k][rows]``. Fancy indexing copies, so a result that keeps a
    row pins its own rows and not the snapshot."""
    idx = np.asarray(rows, np.int64)
    sub = {k: snap[k][idx] for k in _OUT_KEYS}
    if "theta" in snap:
        sub["theta"] = {k: v[idx] for k, v in snap["theta"].items()}
    return sub


def scatter_rows(final: dict, state: dict, rows: Sequence[int],
                 order: np.ndarray, n: int) -> None:
    """The offline compacted run's inverse scatter: write lane rows
    ``rows`` of ``state`` (``_OUT_KEYS`` and the final warm-start
    ``theta``, which the prior bank records) into scenario slots
    ``order[r]`` of ``final``, host arrays of ``n`` rows made at first
    use. Rows whose ``order`` is -1, padding lanes, are left out."""
    rows = np.asarray([r for r in rows if order[r] >= 0], np.int64)
    if not rows.size:
        return
    slots = order[rows]

    def put(dst, src):
        for k, v in src.items():
            if isinstance(v, dict):
                put(dst.setdefault(k, {}), v)
                continue
            if k not in dst:
                dst[k] = np.zeros((n,) + v.shape[1:], v.dtype)
            dst[k][slots] = v

    put(final, take_rows(fetch_out(state, theta=True), rows))


def _make_body(run_data, grid, wvec, cfg: WholeRunConfig, m: int):
    """One BO iteration over the whole lane batch at dataset bucket ``m``
    — the loop body shared by the single-dispatch program and the
    compacted phase dispatches. ``run_data`` carries the lane-aligned
    inputs: ``params``, ``boundary``, ``budget`` and the precomputed
    static penalty block ``pen``."""
    params = run_data["params"]
    s = run_data["budget"].shape[0]
    pen_static = run_data["pen"]
    surr = smod.resolve(cfg.surrogate, cfg.gp)

    def body(carry):
        st, it = carry
        data = gpm.slice_data(
            dict(x=st["x"], y=st["y"], mask=st["mask"]), m)
        # transfer-learned mean prior: per-lane (mu0, n0) pseudo-
        # observations from the bank. Gated statically — with
        # use_prior=False (bank=None) the fit programs are the exact
        # historical traces
        prior = (dict(mu0=st["prior_mu"], n0=st["prior_n0"])
                 if cfg.use_prior else None)

        def cold_fit(data_, _theta0):
            return surr.fit(data_, prior)

        def warm_fit(data_, theta0_):
            return surr.fit_from(data_, theta0_, prior)
        # a lane is cold-seeded on its FIRST post-init body iteration —
        # the per-lane generalization of the old global iteration-0
        # flag (for a static batch every lane is unseeded exactly at
        # iteration 0, so the offline programs are bitwise unchanged);
        # a lane admitted mid-stream gets its cold seed the moment it
        # first steps, keeping its theta trajectory identical to the
        # one it would have had in an offline batch
        unseeded = st["active"] & ~st["seeded"]
        any_unseeded = jnp.any(unseeded)
        # iterations where every live scenario is draining its probe
        # queue skip the fit + acquisition entirely (probes bypass the
        # GP in the host engines too). Unseeded lanes always fit: every
        # lane's warm-start carry is seeded by a cold fit of its init
        # design, which keeps each scenario's theta trajectory
        # independent of the batch composition (=> sharding-invariant)
        need_acq = jnp.any(st["active"] & (st["probe_n"] == 0)) | any_unseeded

        def fit_and_maximize(theta0):
            # GP refits: cold on a lane's first fit (no previous
            # hyperparameters), warm-started + adaptive after. A batch
            # mixing unseeded (just-admitted) and seeded lanes pays
            # both fits once and selects per lane — only admission
            # boundaries in the streaming engine hit that branch
            with jax.named_scope("gp_fit"):
                if cfg.warm_start:
                    all_cold = ~jnp.any(st["active"] & st["seeded"])

                    def mixed_fit(data_, theta0_):
                        gp_c, steps_c = cold_fit(data_, theta0_)
                        gp_w, steps_w = warm_fit(data_, theta0_)
                        gp = jax.tree.map(partial(_sel, st["seeded"]),
                                          gp_w, gp_c)
                        return gp, jnp.where(st["seeded"], steps_w,
                                             steps_c)

                    gp_b, steps = jax.lax.cond(
                        all_cold, cold_fit,
                        lambda d, t0: jax.lax.cond(any_unseeded, mixed_fit,
                                                   warm_fit, d, t0),
                        data, theta0)
                else:
                    gp_b, steps = cold_fit(data, theta0)
            with jax.named_scope("acquisition"):
                a_acq = acquire(gp_b)
            return gp_b["theta"], steps, a_acq

        def acquire(gp_b):
            cand_b = jax.vmap(
                lambda p1, b1, a1, h1: assemble_candidates_dev(
                    p1, grid, b1, a1, h1, cfg.constraint_aware))(
                    params, run_data["boundary"], st["best_a"],
                    st["has_best"])

            live_ev = (jnp.arange(cfg.budget_max)[None, :]
                       < st["n"][:, None])
            ev_min = jnp.min(jnp.where(live_ev, st["ev_u"], jnp.inf),
                             axis=1)
            bf = jnp.where(jnp.isfinite(st["best_u"]), st["best_u"],
                           ev_min)
            if cfg.use_schedules:
                t_norm = ((st["n"] - cfg.n_init).astype(jnp.float32)
                          / jnp.maximum(run_data["budget"] - 1, 1))
            else:
                t_norm = jnp.zeros((s,), jnp.float32)
            lam_b = _sched(wvec["lam_base0"], wvec["lam_baseT"], t_norm)
            lam_g = _sched(wvec["lam_g0"], wvec["lam_gT"], t_norm)

            n_stat = pen_static.shape[1]
            pen_b = jnp.concatenate([
                pen_static,
                jax.vmap(jc.penalty)(params, cand_b[:, n_stat:]),
            ], axis=1)

            def one_max(gp, p1, c, bf1, lb1, lg1, pen1):
                a, _, _ = _maximize_core(
                    gp, p1, c, bf1, lb1, lg1, wvec["lam_p"],
                    wvec["beta"], jnp.float32(REFINE_LR), REFINE_STEPS,
                    penalties=pen1, surrogate=cfg.surrogate)
                return a
            return jax.vmap(one_max)(gp_b, params, cand_b, bf,
                                     lam_b, lam_g, pen_b)

        def probe_only(theta0):
            return (theta0, jnp.zeros((s,), jnp.int32),
                    jnp.zeros((s, 2), jnp.float32))

        theta, steps, a_acq = jax.lax.cond(
            need_acq, fit_and_maximize, probe_only, st["theta"])

        # probe-or-acquisition select + FIFO pop (probes bypass the
        # GP, matching ScenarioState.drain_probes' eval order)
        use_probe = st["probe_n"] > 0
        a_next = jnp.where(use_probe[:, None], st["probe_q"][:, 0],
                           a_acq)
        st2 = dict(st)
        st2["probe_q"] = jnp.where(use_probe[:, None, None],
                                   jnp.roll(st["probe_q"], -1, axis=1),
                                   st["probe_q"])
        st2["probe_n"] = st["probe_n"] - use_probe.astype(jnp.int32)
        # a lane's warm-start carry advances only on ITS acquisition
        # iterations (plus its own first-iteration cold seed), so the
        # theta trajectory is a function of the lane's own eval
        # sequence — independent of batch composition and sharding
        upd = ~st["seeded"] | ~use_probe
        st2["theta"] = jax.tree.map(partial(_sel, upd), theta,
                                    st["theta"])
        st2["fit_steps"] = st["fit_steps"] + jnp.where(upd, steps, 0)
        st2["fit_calls"] = st["fit_calls"] + upd.astype(jnp.int32)
        # every lane stepped this iteration is seeded from now on
        # (frozen lanes keep their flag via the freeze select below)
        st2["seeded"] = jnp.ones_like(st["seeded"])
        with jax.named_scope("oracle_step"):
            st2 = jax.vmap(lambda s1, a, p1, b: _step(s1, a, p1, b, cfg))(
                st2, a_next, params, run_data["budget"])
            # divergence quarantine: a lane whose GP dataset went non-finite
            # (a poisoned observation) must not fit on it — the lane's step
            # is suppressed via the freeze select below, its `fault` flag
            # raises and it deactivates: a retirement event the phase-loop
            # exits surface to the host serve loop, which escalates (requeue /
            # re-seed -> scrub -> degraded retirement). Healthy data is
            # always finite, so `bad` is all False and the select keeps the
            # historical bitwise behavior; the strict detector additionally
            # flags diverged refit carries / chosen points (opt-in — organic
            # warm-fit divergence was historically survivable).
            bad = st["active"] & (
                jnp.any(st["mask"] & ~jnp.isfinite(st["y"]), axis=1)
                | jnp.any(st["mask"]
                          & ~jnp.all(jnp.isfinite(st["x"]), axis=-1), axis=1))
            if cfg.fault_on_divergence:
                bad = bad | (st["active"] & (
                    (~gpm.theta_finite(theta) & upd)
                    | ~jnp.all(jnp.isfinite(a_next), axis=1)))
            # freeze finished scenarios (early-stop masking) + faulted lanes
            new = jax.tree.map(partial(_sel, st["active"] & ~bad), st2, st)
            new["fault"] = st["fault"] | bad
            new["active"] = new["active"] & ~bad
        return new, it + 1

    return body


def _final_bucket(cfg: WholeRunConfig) -> int:
    return gpm.bucket_size(min(cfg.budget_max, cfg.gp.max_points),
                           cfg.gp.max_points)


def _whole_run(stacked, grid, wvec, cfg: WholeRunConfig):
    """Init design + every BO iteration for the whole scenario batch, as
    one traced program (callers jit / shard_map it).

    The loop runs in dataset-bucket *phases* (16/32/48/64 rows, same
    ``gp.DATASET_BUCKETS`` the host engine uses): within phase ``m`` the
    GP fits and posteriors slice the first ``m`` rows of the padded
    dataset — exact w.r.t. the masked kernel — and the loop falls through
    to the next bucket once any scenario outgrows it, so early iterations
    never pay the full ``max_points``^3 Cholesky.

    Returns ``(outputs, n_iters)`` — the total body-step count feeds the
    live-lane occupancy accounting (every step computes all S lanes).
    """
    params = stacked["params"]

    state, pen = _init_run_core(stacked, grid, cfg)

    run_data = dict(params=params, boundary=stacked["boundary"],
                    budget=stacked["budget"], pen=pen)

    m_final = _final_bucket(cfg)
    phases = [b for b in gpm.DATASET_BUCKETS if b < m_final] + [m_final]

    carry = (state, jnp.int32(0))
    for m in phases:
        last = m == phases[-1]

        def cond(carry, m=m, last=last):
            st, it = carry
            ok = jnp.any(st["active"]) & (it < cfg.budget_max)
            if not last:           # fall through once a dataset outgrows m
                ok = ok & (jnp.max(st["n_pts"]) <= m)
            return ok

        carry = jax.lax.while_loop(cond, _make_body(run_data, grid, wvec,
                                                    cfg, m), carry)
    state, n_iters = carry
    out = {k: state[k] for k in _OUT_KEYS}
    # the final warm-start carry rides along for the prior bank's lane-
    # retirement recording (a nested dict leaf — result_from_row and the
    # _OUT_KEYS consumers ignore it)
    out["theta"] = state["theta"]
    return out, n_iters


whole_run = jax.jit(_whole_run, static_argnames=("cfg",))


# -- lane-compaction phase programs (host-driven dispatch sequence) ----------

def _apply_stacked_prior(state, stacked, cfg: WholeRunConfig):
    """Install the staged prior-bank payload into freshly initialized
    lanes: the per-lane mean prior always, and — on the warm-start path —
    the banked theta as the warm carry of hit lanes, which enter
    ``seeded`` so their first fit is a warm refit from the transferred
    hyperparameters instead of a cold MLL climb. Miss lanes (and
    ``use_prior=False`` programs, structurally) keep the cold path
    bitwise."""
    if not cfg.use_prior or "prior_n0" not in stacked:
        return state
    state = dict(state,
                 prior_mu=stacked["prior_mu"].astype(jnp.float32),
                 prior_n0=stacked["prior_n0"].astype(jnp.float32))
    if cfg.warm_start:
        hit = stacked["bank_hit"]
        theta = jax.tree.map(
            lambda t0, t: _sel(hit, t0.astype(t.dtype), t),
            stacked["theta0"], state["theta"])
        state = dict(state, theta=theta, seeded=state["seeded"] | hit)
    return state


def _init_run_core(stacked, grid, cfg: WholeRunConfig):
    params = stacked["params"]
    s = stacked["budget"].shape[0]
    state = jax.vmap(lambda st1, p1, pts, b: _one_init(st1, p1, pts, b, cfg))(
        _init_state(s, cfg), params, stacked["init_pts"], stacked["budget"])
    state = _apply_stacked_prior(state, stacked, cfg)
    return state, _pen_static(params, grid, stacked["boundary"])


@partial(jax.jit, static_argnames=("cfg",))
def init_run(stacked, grid, cfg: WholeRunConfig):
    """The init design as its own dispatch: returns the full-lane state
    plus the static penalty block (both lane-aligned, so the compaction
    gather permutes them together with ``params``/``boundary``)."""
    return _init_run_core(stacked, grid, cfg)


@partial(jax.jit, static_argnames=("cfg", "seed_theta"))
def admit_init(stacked, grid, cfg: WholeRunConfig, seed_theta: bool):
    """Admission staging dispatch: the init design plus (on the
    warm-start path) the cold seed of each admitted lane's GP carry —
    the same cold fit of the init-design dataset (at the init bucket)
    that iteration 0 of the offline program performs, pulled forward to
    admission time so a long-lived server's body only ever pays warm
    refits. Seeded lanes enter the pool with ``seeded=True``; the body
    then warm-fits from a (typically converged) cold theta on the
    lane's first acquisition — the streaming warm path's only
    divergence from the offline program, inside the studied warm
    tolerance by the same argument as warm refits themselves."""
    state, pen = _init_run_core(stacked, grid, cfg)
    if seed_theta:
        surr = smod.resolve(cfg.surrogate, cfg.gp)
        m = gpm.bucket_size(min(cfg.n_init, cfg.gp.max_points),
                            cfg.gp.max_points)
        data = gpm.slice_data(
            dict(x=state["x"], y=state["y"], mask=state["mask"]), m)
        prior = (dict(mu0=state["prior_mu"], n0=state["prior_n0"])
                 if cfg.use_prior else None)
        if cfg.use_prior and cfg.warm_start and "bank_hit" in stacked:
            # bank-hit lanes seed with a warm refit FROM the banked
            # theta (installed by _apply_stacked_prior) — the transfer
            # path; misses pay the historical cold seed
            hit = stacked["bank_hit"]
            model_c, steps_c = surr.fit(data, prior)
            model_w, steps_w = surr.fit_from(data, state["theta"], prior)
            theta = jax.tree.map(partial(_sel, hit),
                                 model_w["theta"], model_c["theta"])
            steps = jnp.where(hit, steps_w, steps_c)
        else:
            model, steps = surr.fit(data, prior)
            theta = model["theta"]
        state = dict(
            state, theta=theta,
            fit_steps=state["fit_steps"] + steps,
            fit_calls=state["fit_calls"] + 1,
            seeded=jnp.ones_like(state["seeded"]))
    return state, pen


@partial(jax.jit, static_argnames=("cfg", "m", "last"))
def run_phase(run_data, state, it, grid, wvec, cfg: WholeRunConfig,
              m: int, last: bool):
    """One compaction phase: the shared loop body at dataset bucket ``m``,
    iterated until (a) every lane is done, (b) a dataset outgrows the
    bucket, or (c) the live-lane count falls to half the lane capacity —
    at which point the host driver compacts and re-dispatches the next
    phase as a smaller program. ``it`` is the global iteration counter
    carried across dispatches (iteration 0 seeds the warm-start carry)."""
    s = run_data["budget"].shape[0]

    def cond(carry):
        st, it_ = carry
        live = jnp.sum(st["active"])
        ok = (live > 0) & (it_ < cfg.budget_max)
        if not last:
            # fall through once a LIVE dataset outgrows m. Retired lanes
            # are masked out: the driver sizes m from live lanes only, so
            # a dead lane whose dataset already outgrew the bucket (while
            # the live count hasn't halved yet) must not flip this exit —
            # it would make the dispatch run zero iterations and wedge
            # the host loop. Exact either way: frozen lanes never fit.
            live_pts = jnp.where(st["active"], st["n_pts"], 0)
            ok = ok & (jnp.max(live_pts) <= m)
        if s > 1:                  # exit to compact once occupancy halves
            ok = ok & (2 * live > s)
        return ok

    return jax.lax.while_loop(cond, _make_body(run_data, grid, wvec, cfg, m),
                              (state, it))


gather_lanes = jax.jit(gpm.take_lanes)


def gather_live_lanes(state, run_data, live: np.ndarray, s_next: int):
    """The compaction gather shared by the offline compaction driver and
    the streaming pool shrink: permute the surviving lanes (``live``,
    original row indices) into a dense prefix of a ``s_next``-lane
    layout — state pytree AND lane-aligned inputs — padding with
    duplicates of the first survivor, which stay deactivated. Returns
    ``(state, run_data, keep)`` where ``keep`` is the row permutation
    the caller applies to its own host-side lane bookkeeping."""
    keep = np.concatenate([live, np.repeat(live[:1], s_next - live.size)])
    idx = jnp.asarray(keep)
    state = gather_lanes(state, idx)
    run_data = gather_lanes(run_data, idx)
    if live.size < s_next:       # pad duplicates stay frozen
        state = dict(state, active=state["active"]
                     & (jnp.arange(s_next) < live.size))
    return state, run_data, keep


@partial(jax.jit, static_argnames=("k",))
def _fresh_tail(state, k: int):
    """Zero the bookkeeping of every row past the first ``k``: resized
    pools pad with gathered duplicates of occupied rows, and a duplicate
    must not inherit its source's generation / fault / seed flags — the
    admission scatter overwrites everything else but *increments* the
    generation, so a stale copy would break the (pool, lane, gen)
    attribution of its next occupant."""
    s = state["active"].shape[0]
    tail = jnp.arange(s) >= k
    z32 = jnp.zeros((s,), jnp.int32)
    return dict(state,
                active=state["active"] & ~tail,
                fault=state["fault"] & ~tail,
                seeded=state["seeded"] & ~tail,
                gen=jnp.where(tail, z32, state["gen"]))


def resize_lanes(state, run_data, occ: np.ndarray, s_next: int):
    """Elastic pool resize — the compaction gather run in *either*
    direction: permute the occupied rows (``occ``, original indices)
    into a dense prefix of an ``s_next``-lane layout (state pytree AND
    lane-aligned inputs), growing or shrinking the pool between
    dispatches with zero recompilation beyond the per-width program
    cache. Tail rows (gathered via :func:`gp.pad_lanes_index`-style
    duplicates of the first occupant, or of row 0 when the pool is
    empty) come back deactivated with fresh generation/fault/seed
    bookkeeping, ready for an ordinary admission scatter. Returns
    ``(state, run_data)``; the caller permutes its host lane maps with
    ``occ`` itself."""
    if occ.size > s_next:
        raise ValueError(f"{occ.size} occupied lanes cannot fit a "
                         f"{s_next}-lane pool")
    src = np.zeros(s_next, np.int64)
    src[:occ.size] = occ
    idx = jnp.asarray(src)
    state = gather_lanes(state, idx)
    run_data = gather_lanes(run_data, idx)
    return _fresh_tail(state, int(occ.size)), run_data


# -- streaming admission programs (runtime/stream.py drives these) -----------

@partial(jax.jit, static_argnames=("cfg", "m", "last"))
def stream_phase(run_data, state, it, live0, grid, wvec, cfg: WholeRunConfig,
                 m: int, last: bool):
    """One serving-loop phase: the shared loop body at dataset bucket
    ``m``, iterated until (a) every lane is done, (b) a live dataset
    outgrows the bucket, or (c) ANY lane retires (``live`` falls below
    the entry count ``live0``) — the lane-free event the admission queue
    waits on. Unlike :func:`run_phase` the iteration cap is
    per-dispatch (``it`` grows without bound across a stream's life, so
    the offline ``it < budget_max`` safety cap would wrongly halt a
    long-lived server; an active lane must retire within ``budget_max``
    steps, which bounds each dispatch instead)."""
    it0 = it

    def cond(carry):
        st, it_ = carry
        live = jnp.sum(st["active"])
        ok = (live > 0) & (it_ - it0 < cfg.budget_max) & (live >= live0)
        if not last:
            # live datasets only (see run_phase: a retired lane's stale
            # dataset must not wedge the dispatch at zero iterations)
            live_pts = jnp.where(st["active"], st["n_pts"], 0)
            ok = ok & (jnp.max(live_pts) <= m)
        return ok

    return jax.lax.while_loop(cond, _make_body(run_data, grid, wvec, cfg, m),
                              (state, it))


@jax.jit
def admit_lanes(state, run_data, new_state, new_run_data, lanes):
    """Admission scatter — the inverse of the compaction gather: write
    the first ``k = len(lanes)`` rows of a freshly initialized
    mini-batch (state pytree AND lane-aligned inputs: ``params``,
    ``boundary``, ``budget``, the static penalty block) into the given
    freed lanes of a running pool, in place. The lane generation
    counter increments instead of being overwritten, so ledger
    snapshots remain attributable to one (lane, generation) occupant."""
    k = lanes.shape[0]

    def put(big, new):
        return big.at[lanes].set(new[:k])

    gen = state["gen"].at[lanes].add(1)
    state = dict(jax.tree.map(put, state, new_state), gen=gen)
    return state, jax.tree.map(put, run_data, new_run_data)


@jax.jit
def retire_lanes(state, run_data, lanes):
    """Force-retire the given lanes through the existing retirement
    machinery (deactivate; the next phase exit / collect flushes them),
    installing the best-effort degraded answer for lanes that never
    found a feasible incumbent: the feasible projection of the
    search-space center (``jax_cost.fallback_answer``). Used for
    deadline preemption of hopeless lanes and for the terminal rung of
    the divergence-quarantine ladder — ``fault`` clears so the flush
    path treats the lane as ordinarily retired."""
    params_rows = jax.tree.map(lambda v: v[lanes], run_data["params"])
    a, u, feas = jax.vmap(jc.fallback_answer)(
        params_rows, state["best_a"][lanes], state["has_best"][lanes])
    hb = state["has_best"][lanes]
    state = dict(state)
    state["best_a"] = state["best_a"].at[lanes].set(a)
    state["best_u"] = state["best_u"].at[lanes].set(
        jnp.where(hb, state["best_u"][lanes],
                  jnp.where(feas, u, -jnp.inf)))
    state["has_best"] = state["has_best"].at[lanes].set(hb | feas)
    state["active"] = state["active"].at[lanes].set(False)
    state["fault"] = state["fault"].at[lanes].set(False)
    return state


@partial(jax.jit, static_argnames=("cfg", "scrub"))
def quarantine_lanes(state, lanes, cfg: WholeRunConfig, scrub: bool):
    """One repair rung of the divergence-quarantine ladder, applied in
    place to faulted lanes: reset the lanes' hyperparameter carry to the
    cold init and clear ``seeded`` so their next body iteration performs
    a fresh cold fit (the re-seed rung); with ``scrub=True`` additionally
    drop non-finite observations from their GP datasets
    (``gp.scrub_dataset`` — the cold-refit rung for a poisoned dataset).
    The lanes reactivate with ``fault`` cleared and their early-stop
    counter reset; ledger, incumbent and generation are untouched (the
    same occupant continues)."""
    th0 = smod.resolve(cfg.surrogate, cfg.gp).init_theta()
    k = lanes.shape[0]
    state = dict(state)
    state["theta"] = jax.tree.map(
        lambda v, v0: v.at[lanes].set(
            jnp.broadcast_to(v0, (k,)).astype(v.dtype)),
        state["theta"], th0)
    if scrub:
        data = gpm.scrub_dataset(
            dict(x=state["x"][lanes], y=state["y"][lanes],
                 mask=state["mask"][lanes]))
        state["x"] = state["x"].at[lanes].set(data["x"])
        state["y"] = state["y"].at[lanes].set(data["y"])
        state["mask"] = state["mask"].at[lanes].set(data["mask"])
    state["seeded"] = state["seeded"].at[lanes].set(False)
    state["fault"] = state["fault"].at[lanes].set(False)
    state["active"] = state["active"].at[lanes].set(True)
    state["n_c"] = state["n_c"].at[lanes].set(0)
    return state


# -- host-side input staging (shared by the offline and streaming engines) ---

def stage_scenario(sc: Scenario, l_pad: int, n_init: int,
                   constraint_aware: bool, fill: np.ndarray,
                   bank: Optional[PriorBank] = None) -> dict:
    """Host staging of ONE scenario into the padded-lane layout: device
    constraint params (at the scenario's own ``L`` — :func:`jax_cost
    .stack_params` pads to the batch ``l_pad``), the seeded init design,
    and the boundary candidate block padded to ``l_pad`` rows with
    ``fill``. The single staging path for offline batches and streaming
    admissions, so an admitted lane is bitwise the lane an offline
    batch would have staged.

    With a prior ``bank`` the staging additionally queries the
    transfer-learned store: on a hit the staged dict carries the banked
    (theta, mean-prior) payload and — with incumbent seeding on — the
    FIRST init-design point is replaced by the historical incumbent
    (projected feasible for this scenario's channel), so the warm run
    evaluates near the banked optimum immediately. A miss (or
    ``bank=None``) stages the bitwise-historical layout with a zeroed
    prior payload."""
    pb = sc.problem
    if pb.L > l_pad:
        raise ValueError(f"scenario L={pb.L} exceeds the engine l_pad="
                         f"{l_pad}")
    rng = np.random.default_rng(sc.seed)
    pts = _init_grid(n_init, rng)
    if constraint_aware:
        pts = np.stack([pb.project_feasible(a) for a in pts])
    prior_row, seed_a = stage_prior(sc, bank)
    if seed_a is not None:
        if constraint_aware:
            seed_a = pb.project_feasible(seed_a)
        pts = pts.copy()
        pts[0] = np.clip(seed_a, 0.0, 1.0)
    bpad = np.repeat(fill, l_pad, axis=0)
    if constraint_aware:
        b = pb.boundary_candidates()
        if len(b):
            bpad = bpad.copy()
            bpad[:len(b)] = b[:pb.L]
    return dict(params=pb.jax_params(), budget=sc.budget, init_pts=pts,
                boundary=bpad, **prior_row)


def stack_staged(staged: Sequence[dict], l_pad: int, pad_to: int) -> dict:
    """Stack per-scenario staging dicts (:func:`stage_scenario`) into the
    stacked input pytree of the whole-run programs, repeating row 0 out
    to ``pad_to`` lanes (padding rows are deactivated by the callers)."""
    staged = list(staged) + [staged[0]] * (pad_to - len(staged))
    return dict(
        # per-layer surfaces pad to the batch width at stack time
        # (bitwise-equal to pre-padding each scenario's params)
        params=jc.stack_params([st["params"] for st in staged],
                               l_pad=l_pad),
        budget=jnp.asarray(np.asarray([st["budget"] for st in staged]),
                           jnp.int32),
        init_pts=jnp.asarray(np.stack([st["init_pts"] for st in staged]),
                             jnp.float32),
        boundary=jnp.asarray(np.stack([st["boundary"] for st in staged]),
                             jnp.float32),
        # prior-bank payload (zeros on miss / bank=None — staged dicts
        # from older callers without the keys default to the cold path)
        prior_mu=jnp.asarray(np.asarray(
            [st.get("prior_mu", 0.0) for st in staged]), jnp.float32),
        prior_n0=jnp.asarray(np.asarray(
            [st.get("prior_n0", 0.0) for st in staged]), jnp.float32),
        bank_hit=jnp.asarray(np.asarray(
            [st.get("bank_hit", False) for st in staged]), bool),
        theta0={k: jnp.asarray(np.asarray(
            [st.get("theta0", {}).get(k, 0.0) for st in staged]),
            jnp.float32) for k in _THETA_KEYS},
    )


def acq_wvec(w: AcqWeights) -> dict:
    """Acquisition weights as the traced-scalar dict the device programs
    take (shared by the offline engine and the streaming server)."""
    return dict(lam_base0=jnp.float32(w.lam_base0),
                lam_baseT=jnp.float32(w.lam_baseT),
                lam_g0=jnp.float32(w.lam_g0),
                lam_gT=jnp.float32(w.lam_gT),
                lam_p=jnp.float32(w.lam_p), beta=jnp.float32(w.beta))


def result_from_row(out: dict, i: int, sc: Scenario) -> BOResult:
    """Build one scenario's ``BOResult`` from row ``i`` of an
    ``_OUT_KEYS`` snapshot (host numpy) — shared by the offline result
    unpacking and the streaming per-lane retirement flush."""
    n = int(out["n"][i])
    has_best = bool(out["has_best"][i])
    best_a = (np.asarray(out["best_a"][i], np.float64) if has_best
              else None)
    best_acc = 0.0
    if has_best:
        best_acc = float(sc.problem._accuracy(
            *sc.problem.denormalize(best_a))[1])
    return BOResult(
        best_a, float(out["best_u"][i]), best_acc, n,
        [float(v) for v in out["ev_u"][i][:n]],
        [float(v) for v in out["ev_acc"][i][:n]],
        [bool(v) for v in out["ev_feas"][i][:n]],
        [float(v) for v in out["ev_trace"][i][:n]])


@partial(jax.jit, static_argnames=("cfg", "mesh"))
def whole_run_sharded(stacked, grid, wvec, cfg: WholeRunConfig, mesh: Mesh):
    """Scenario-sharded whole run: the leading S axis splits across the
    1-D ``("scen",)`` mesh; each device steps its own ``while_loop`` over
    its shard (the per-scenario programs are embarrassingly parallel, so
    there are no collectives). Shards exit their loops independently, so
    packing like-budget lanes onto the same shard (``pack=True``) lets a
    shard full of early finishers retire its device early.

    The per-lane warm-start gating makes each scenario's trajectory
    independent of batch *composition*, but XLA may reassociate f32
    reductions for different local batch sizes, so sharded results are
    guaranteed equivalent to the unsharded program only within the
    studied trace tolerance (empirically bitwise on multi-lane shards).
    """
    f = jax.shard_map(lambda st, g, w: _whole_run(st, g, w, cfg)[0],
                      mesh=mesh, in_specs=(PS("scen"), PS(), PS()),
                      out_specs=PS("scen"), check_vma=False)
    return f(stacked, grid, wvec)


def scenario_sharding(mesh: Mesh) -> NamedSharding:
    """Leading-axis NamedSharding for the stacked scenario pytree."""
    return NamedSharding(mesh, PS("scen"))


# -- host wrapper ------------------------------------------------------------

class WholeRunBayesSplitEdge:
    """Single-dispatch Bayes-Split-Edge over a scenario batch.

    Same surface as ``BatchedBayesSplitEdge`` (one ``BOResult`` per
    scenario, trace-equivalent to sequential ``BayesSplitEdge.run`` up to
    f32-on-device numerics), plus:

    * ``warm_start`` — warm-started adaptive GP refits (default on;
      ``False`` restores bitwise cold-fit traces).
    * ``mesh`` — a 1-D ``("scen",)`` mesh to shard the scenario axis
      across devices (see :func:`repro.distributed.sharding
      .scenario_mesh`).
    * ``compact`` — between-phase lane compaction (default on; ignored
      under ``mesh``, where shards already exit independently): the run
      becomes a short sequence of phase dispatches, each sized to the
      next power-of-2 over the surviving lanes, so heterogeneous-budget
      batches stop paying for early-stopped lanes. A pure re-scheduling
      of the same per-lane programs (``compact=False`` restores the
      one-dispatch whole-run program).
    * ``pack`` — architecture-aware lane packing: lanes sort by
      ``(n_layers, budget)`` so lanes that die together live together
      (and like-``L`` lanes share shards under ``mesh``). Purely an
      internal staging layout: ``self.scenarios``, the returned results
      and the raw ledger all stay aligned with the caller's order.
    """

    name = "WholeRun-Bayes-Split-Edge"

    def __init__(self, scenarios: Sequence[Scenario],
                 config: Optional[EngineConfig] = None, *,
                 mesh: Optional[Mesh] = None,
                 bank: Optional[PriorBank] = None, **kw):
        config = resolve_config(config, kw, "WholeRunBayesSplitEdge")
        if kw:
            raise TypeError(f"WholeRunBayesSplitEdge() got unexpected "
                            f"keyword arguments {sorted(kw)}")
        if not scenarios:
            raise ValueError("need at least one scenario")
        scenarios = list(scenarios)
        # architecture-aware lane packing is pure internal staging:
        # `self.scenarios`, results and the raw ledger all stay in the
        # caller's order; only `_staged` (the device lane layout) sorts
        self._pack_order = None
        self._staged = scenarios
        if config.pack:
            from repro.distributed.sharding import pack_order
            self._pack_order = pack_order(scenarios)
            self._staged = [scenarios[i] for i in self._pack_order]
        # mixed-architecture batches: pad every per-layer surface to the
        # batch-wide L_max (a single-arch batch pads to its own L, which
        # is the bit-identical unpadded layout)
        l_max = max(sc.problem.L for sc in scenarios)
        self.l_pad = l_max if config.l_pad is None else config.l_pad
        if self.l_pad < l_max:
            raise ValueError(f"l_pad={config.l_pad} < batch "
                             f"L_max={l_max}")
        self.config = config
        self.scenarios = scenarios
        self.n_init = config.n_init
        self.n_max_repeat = config.n_max_repeat
        self.weights = config.acq_weights()
        self.gp_cfg = config.gp_cfg
        self.grid = candidate_grid(config.grid_n)
        self.constraint_aware = config.constraint_aware
        self.use_schedules = config.use_schedules
        self.warm_start = config.warm_start
        self.surrogate = config.surrogate
        self.mesh = mesh
        self.compact = config.compact
        self.gp_feasible_only = config.constraint_aware
        # transfer-learned prior bank: queried at staging, recorded into
        # at run exit (None keeps every program bitwise-historical)
        self.bank = bank

    # -- input staging -------------------------------------------------------
    def _pad_to(self) -> int:
        """Scenario count padded to a power of 2 (bounded trace count), and
        to a multiple of the mesh size when sharding."""
        s = _next_pow2(len(self.scenarios))
        if self.mesh is not None:
            d = self.mesh.size
            s = max(s, d)
            if s % d:
                s = (s // d + 1) * d
        return s

    def _stacked(self) -> dict:
        staged = [stage_scenario(sc, self.l_pad, self.n_init,
                                 self.constraint_aware, self.grid[:1],
                                 bank=self.bank)
                  for sc in self._staged]
        return stack_staged(staged, self.l_pad, self._pad_to())

    # -- compaction driver ---------------------------------------------------
    def _run_compacted(self, stacked, grid, wvec, cfg: WholeRunConfig):
        """Phase-dispatch sequence with between-phase lane compaction.

        After every phase dispatch the driver reads back the (tiny)
        ``active``/``n_pts`` vectors, gathers surviving lanes into a
        dense prefix at the next power-of-2 lane count (an on-device
        permutation of the whole state pytree + lane-aligned inputs),
        and snapshots retiring lanes' outputs into their original
        scenario rows — the inverse scatter that makes the whole thing a
        pure permutation of the uncompacted program's results.
        """
        n_real = len(self.scenarios)
        s0 = stacked["budget"].shape[0]
        state, pen = init_run(stacked, grid, cfg)
        run_data = dict(params=stacked["params"],
                        boundary=stacked["boundary"],
                        budget=stacked["budget"], pen=pen)
        if s0 > n_real:
            # power-of-2 padding lanes duplicate scenario 0 and never
            # contribute results — deactivate them so the first
            # compaction drops them instead of stepping them
            state = dict(state, active=state["active"]
                         & (jnp.arange(s0) < n_real))
        order = np.arange(s0)       # lane row -> original scenario index
        order[n_real:] = -1
        final: dict = {}

        m_final = _final_bucket(cfg)
        it = jnp.int32(0)
        it_host = 0
        lane_log: list = []
        while True:
            active = np.asarray(state["active"])
            n_pts = np.asarray(state["n_pts"])
            live = np.flatnonzero(active)
            if live.size == 0:
                break
            m = gpm.bucket_size(int(n_pts[live].max()), cfg.gp.max_points)
            s_next = _next_pow2(live.size)
            if s_next < active.shape[0]:
                # retire exactly the lanes about to drop
                scatter_rows(final, state,
                             np.setdiff1d(np.arange(active.shape[0]), live),
                             order, n_real)
                state, run_data, keep = gather_live_lanes(
                    state, run_data, live, s_next)
                order = np.where(np.arange(s_next) < live.size,
                                 order[keep], -1)
            state, it = run_phase(run_data, state, it, grid, wvec, cfg,
                                  m, m >= m_final)
            it_new = int(it)
            lane_log.append(dict(lanes=int(run_data["budget"].shape[0]),
                                 live=int(live.size), bucket=m,
                                 iters=it_new - it_host))
            it_host = it_new
        scatter_rows(final, state, np.arange(state["n"].shape[0]), order,
                     n_real)
        slots = sum(log["lanes"] * log["iters"] for log in lane_log)
        self._lane_stats = dict(
            n_dispatches=len(lane_log), lane_slots=slots,
            lane_log=lane_log)
        return final

    def run_config(self) -> WholeRunConfig:
        """The static program configuration ``run`` compiles against."""
        return WholeRunConfig(
            n_init=self.n_init, n_max_repeat=self.n_max_repeat,
            # the ledger must hold the full init design even when a
            # scenario's budget is below n_init (the host engines still
            # evaluate all n_init points before stopping)
            budget_max=max(max(sc.budget for sc in self.scenarios),
                           self.n_init),
            l_pad=self.l_pad,
            constraint_aware=self.constraint_aware,
            gp_feasible_only=self.gp_feasible_only,
            use_schedules=self.use_schedules, warm_start=self.warm_start,
            gp=self.gp_cfg, surrogate=self.surrogate,
            use_prior=self.bank is not None)

    def run(self) -> List[BOResult]:
        cfg = self.run_config()
        wvec = acq_wvec(self.weights)
        stacked = self._stacked()
        grid = jnp.asarray(self.grid, jnp.float32)
        self._lane_stats = {}
        if self.mesh is not None:
            sh = scenario_sharding(self.mesh)
            stacked = jax.device_put(stacked, sh)
            out = whole_run_sharded(stacked, grid, wvec, cfg, self.mesh)
            out = jax.tree.map(np.asarray, out)  # host-side gather
        elif self.compact:
            out = self._run_compacted(stacked, grid, wvec, cfg)
        else:
            out, n_iters = whole_run(stacked, grid, wvec, cfg)
            out = jax.tree.map(np.asarray, out)
            self._lane_stats = dict(
                n_dispatches=1,
                lane_slots=int(n_iters) * stacked["budget"].shape[0],
                lane_log=[dict(lanes=stacked["budget"].shape[0],
                               live=len(self.scenarios),
                               iters=int(n_iters))])
        # raw device ledger (incl. per-eval split layers) — lets tests and
        # gates audit that padded tail splits never entered the ledger.
        # Row i aligns with self.scenarios[i] (the caller's order): packed
        # staging is inverted here, like the results below
        if self._pack_order is not None:
            rowmap = np.empty(len(self._pack_order), np.int64)
            rowmap[self._pack_order] = np.arange(len(self._pack_order))
            # tree-aware: `out` holds nested leaves (the theta carry)
            self._last_raw = jax.tree.map(lambda v: v[rowmap], out)
        else:
            self._last_raw = out
        # fold retired runs into the transfer bank (frozen banks, runs
        # without a feasible incumbent and non-finite fits are skipped
        # inside record_result). Rows align with self._staged
        if self.bank is not None:
            th = out["theta"]
            for i in range(len(self._staged)):
                n = int(out["n"][i])
                self.bank.record_result(
                    self._staged[i],
                    (th["log_ls"][i], th["log_sv"][i], th["log_nv"][i]),
                    out["ev_u"][i][:n], out["ev_feas"][i][:n],
                    out["best_a"][i], out["best_u"][i],
                    bool(out["has_best"][i]))

        live = len(self.scenarios)
        if self._lane_stats:
            evals = int(np.sum(out["n"][:live])) - live * self.n_init
            slots = self._lane_stats["lane_slots"]
            self._lane_stats["loop_evals"] = evals
            self._lane_stats["occupancy_mean"] = (
                evals / slots if slots else 1.0)
        fc = out["fit_calls"][:live].astype(np.int64)
        fs = out["fit_steps"][:live].astype(np.int64)
        calls, total = int(fc.sum()), int(fs.sum())
        # a lane's first counted refit (iteration 0, if it was active) is
        # the cold seed (cfg.fit_steps Adam steps); the warm-only mean is
        # the per-refit cost after it. Lanes that never fit (e.g.
        # budget == n_init) contribute nothing to either bucket.
        seeded = (fc > 0).astype(np.int64)
        if self.warm_start:
            warm_calls = int((fc - seeded).sum())
            warm_total = int((fs - seeded * self.gp_cfg.fit_steps).sum())
        else:
            warm_calls, warm_total = calls, total
        self._fit_stats = dict(
            fit_calls=calls,
            fit_steps_mean=float(total / calls) if calls else 0.0,
            warm_steps_mean=(float(warm_total / warm_calls)
                             if warm_calls else 0.0))

        results = [result_from_row(out, i, sc)
                   for i, sc in enumerate(self._staged)]
        if self._pack_order is not None:
            # inverse permutation: results return in the caller's order
            from repro.distributed.sharding import unpack_results
            results = unpack_results(results, self._pack_order)
        return results

    def fit_cost_stats(self) -> dict:
        """Adam-step accounting of the last ``run``: total refit calls and
        mean Adam steps per refit (cold fits count ``fit_steps`` each)."""
        return dict(getattr(self, "_fit_stats", {}))

    def lane_stats(self) -> dict:
        """Lane-occupancy accounting of the last ``run`` (empty under
        ``mesh``): computed lane-slots vs live-lane evals in the BO loop
        (``occupancy_mean == 1.0`` means no dead-lane waste), plus the
        per-dispatch lane log of the compaction driver."""
        return dict(getattr(self, "_lane_stats", {}))
