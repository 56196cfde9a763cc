"""The plain float64 reference that decides ``correct``.

It imports nothing of the planner. From the public architecture
description in ``bench/archs/<arch>.json`` it builds the per-split-layer
cost profile (device MACs, server MACs, bytes on the uplink), the
paper's energy and delay model (Eq. 1-4), the constraint budgets, the
channel anchor and the utility oracle, and then holds every solve the
planner emitted to them.

The profile is the architecture kind's own: ``network.kind`` names the
file ``bench/kinds/<kind>.py``, whose ``profile(network)`` returns
``(macs, boundary_bytes, tail_macs)``: the MACs of each of the L split
layers, the bytes that cross the link after split ``l`` for ``l`` =
0..L (index 0 is what the device sends when it runs nothing) and the
MACs that only the server runs, after the last split layer. A kind
module imports only the standard library and numpy. The rest of the
reference is the same for every kind:

* ``ledger_faults`` (exact): the ledger is well formed. Its length is
  within the budget, every split layer is a real one, the answer is the
  best feasible evaluation, the incumbent trace is the running best,
  and no request was answered twice or degraded.
* ``eval_gap``: every evaluation's value against the reference. A
  feasible evaluation at split layer ``l`` has to lie in the band of
  utilities that the feasible powers of ``l`` give, and report the
  layer's quantized accuracy; an infeasible one has to read the hard
  failure (0) or the deadline truncation (the base accuracy). The gap is
  the distance outside that set, over the architecture's base accuracy.
* ``answer_gap``: the reported utility of the answer against the
  oracle at the answer's (split layer, power), over the base accuracy.
* ``unanswered``: the solve has no answer although the reference finds
  a feasible (split layer, power): finding one is what the loop body
  (GP refit, acquisition, refinement, probes) is for.
* ``regret`` (for information): how far the answer's utility lies below
  the best that any feasible point reaches, over the base accuracy.

The constraint tests allow ``TOL`` of relative slack: the planner works
in float32, whose rounding of the energy and delay is about 1e-7.
"""
from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

from bench.lib.spec import load_module

TOL = 1e-5

# Section 6.1 of the paper: Raspberry Pi 4 device, Mac M4 server, an
# OFDM uplink of 240 kHz x 256 subcarriers x 0.8 and N0 = -147 dBm/Hz
KAPPA = 1e-29
DEV_HZ, DEV_ETA = 1.8e9, 2.0
SRV_HZ, SRV_ETA = 4.5e9, 9.0
BANDWIDTH_HZ = 240_000.0 * 256.0 * 0.8
NOISE_W = 10.0 ** ((-147.0 - 30.0) / 10.0) * BANDWIDTH_HZ
EPS_ENERGY = 0.1
QUANTUM = 100.0 / 64.0
COMPLETION_FLOOR = 0.9


class Arch:
    """One architecture's cost profile and calibrated problem."""

    def __init__(self, spec: dict, kinds_dir: Path):
        net = spec["network"]
        kind = load_module(kinds_dir, "kind", net["kind"])
        macs, boundary_bytes, tail = kind.profile(net)
        self.name = spec["arch"]
        self.L = len(macs)
        if len(boundary_bytes) != self.L + 1:
            raise ValueError(f"{self.name}: {len(boundary_bytes)} boundary "
                             f"sizes for {self.L} split layers")
        self.cum = np.concatenate([[0.0], np.cumsum(macs, dtype=np.float64)])
        self.total = float(self.cum[-1] + tail)
        self.bits = 8.0 * np.asarray(boundary_bytes, np.float64)
        self.p_min, self.p_max = map(float, spec["power_w"])
        b, u, a = spec["budgets"], spec["utility"], spec["anchor"]
        self.e_max, self.tau_max = b["e_max_j"], b["tau_max_s"]
        self.base, self.bump = u["base_acc"], u["bump"]
        self.peak, self.sigma = u["peak_layer"], u["sigma"]
        self.gain0_db = self._anchor_gain(a["layer"], a["p_w"])

    # Eq. (2)-(4) ---------------------------------------------------------
    def dev_energy(self, l):
        return KAPPA * self.cum[l] * DEV_HZ ** 2

    def dev_delay(self, l):
        return self.cum[l] / (DEV_HZ * DEV_ETA)

    def srv_delay(self, l):
        return (self.total - self.cum[l]) / (SRV_HZ * SRV_ETA)

    def tx_delay(self, l, p, gain_db):
        rate = BANDWIDTH_HZ * np.log2(1.0 + p * 10.0 ** (gain_db / 10.0)
                                      / NOISE_W)
        with np.errstate(divide="ignore"):
            return np.where(rate > 0, self.bits[l] / np.maximum(rate, 1e-300),
                            np.inf)

    def energy_delay(self, l, p, gain_db):
        tx = self.tx_delay(l, p, gain_db)
        with np.errstate(invalid="ignore"):
            e = self.dev_energy(l) + np.where(np.isfinite(tx), p * tx, np.inf)
        return e, self.dev_delay(l) + tx + self.srv_delay(l)

    def required_power(self, l, gain_db):
        """Least power meeting the deadline at split ``l`` (inf if the
        compute alone misses it)."""
        slack = self.tau_max - self.dev_delay(l) - self.srv_delay(l)
        if slack <= 0:
            return math.inf
        x = 2.0 ** (self.bits[l] / slack / BANDWIDTH_HZ) - 1.0
        return x * NOISE_W / 10.0 ** (gain_db / 10.0)

    def _anchor_gain(self, l, p):
        """Channel gain that makes ``p`` the least feasible power at
        split ``l`` (the paper's Table-1 operating point)."""
        slack = self.tau_max - self.dev_delay(l) - self.srv_delay(l)
        x = 2.0 ** (self.bits[l] / slack / BANDWIDTH_HZ) - 1.0
        return float(10.0 * np.log10(x * NOISE_W / p))

    # the utility oracle --------------------------------------------------
    def full_value(self, l):
        """Utility before the energy term, and the reported accuracy, of
        a run that completes at split ``l``."""
        raw = self.base + self.bump * np.exp(
            -0.5 * ((l - self.peak) / self.sigma) ** 2)
        return raw, np.floor(raw / QUANTUM + 1e-9) * QUANTUM

    def utility(self, l, p, gain_db, tol=0.0):
        """(utility, accuracy, feasible) at split ``l`` and power ``p``;
        ``tol`` loosens every constraint by that relative slack."""
        e, t = self.energy_delay(l, p, gain_db)
        lim_e, lim_t = self.e_max * (1 + tol), self.tau_max * (1 + tol)
        if e > lim_e or t > lim_t / COMPLETION_FLOOR:
            return 0.0, 0.0, False
        if t > lim_t:
            return (self.base, np.floor(self.base / QUANTUM + 1e-9) * QUANTUM,
                    False)
        raw, acc = self.full_value(l)
        return raw - EPS_ENERGY * min(e, self.e_max) / self.e_max, acc, True

    def band(self, l, gain_db, tol=TOL):
        """(low, high) utility of the feasible powers at split ``l``, or
        None where no power is feasible there."""
        p_lo = max(self.p_min, self.required_power(l, gain_db) * (1 - tol))
        if p_lo > self.p_max * (1 + tol):
            return None
        p_lo = min(p_lo, self.p_max)
        e_lo = self.energy_delay(l, p_lo, gain_db)[0]
        if e_lo > self.e_max * (1 + tol):
            return None
        e_hi = min(self.e_max, self.energy_delay(l, self.p_max, gain_db)[0])
        raw, _ = self.full_value(l)
        return (raw - EPS_ENERGY * e_hi / self.e_max,
                raw - EPS_ENERGY * min(e_lo, self.e_max) / self.e_max)

    def optimum(self, gain_db) -> float:
        """The best utility any feasible (split, power) reaches."""
        best = -math.inf
        for l in range(1, self.L + 1):
            b = self.band(l, gain_db, tol=0.0)
            if b is not None:
                best = max(best, b[1])
        return best

    def denormalize(self, a):
        """Normalized point -> (candidate split layers, power). Both
        layers are returned where the layer coordinate sits within float32
        rounding of a half step."""
        a = np.clip(np.asarray(a, np.float64), 0.0, 1.0)
        p = self.p_min + a[0] * (self.p_max - self.p_min)
        x = 1.0 + a[1] * (self.L - 1)
        cands = {int(np.clip(np.rint(x), 1, self.L))}
        if abs(x - math.floor(x) - 0.5) < 1e-5:
            cands |= {int(np.clip(math.floor(x), 1, self.L)),
                      int(np.clip(math.ceil(x), 1, self.L))}
        return sorted(cands), p


def load_archs(archs_dir: Path, names) -> dict:
    """``Arch`` of each ``<archs_dir>/<name>.json``; the kinds' profiles
    are the files of ``kinds/`` beside ``archs_dir``."""
    kinds_dir = Path(archs_dir).parent / "kinds"
    out = {}
    for n in names:
        with open(Path(archs_dir) / f"{n}.json") as f:
            out[n] = Arch(json.load(f), kinds_dir)
    return out


# -- the comparison ----------------------------------------------------------

def check_solve(arch: Arch, gain_offset_db: float, budget: int, n_init: int,
                result, ev_l) -> dict:
    """Hold one emitted solve to the reference. ``result`` carries the
    answer (``best_a``, ``best_utility``, ``best_accuracy``) and the
    ledger (``utilities``, ``accuracies``, ``feasible``,
    ``incumbent_trace``); ``ev_l`` gives each evaluation's split layer.
    Returns the solve's ledger fault count, widest evaluation gap,
    answer gap, regret (the answer's utility below the best feasible
    one, over the base accuracy; None without an answer) and whether it
    went unanswered where a feasible point exists."""
    g = arch.gain0_db + gain_offset_db
    faults = 0
    u = np.asarray(result.utilities, np.float64)
    acc = np.asarray(result.accuracies, np.float64)
    feas = np.asarray(result.feasible, bool)
    trace = np.asarray(result.incumbent_trace, np.float64)
    n = int(result.n_evals)
    ev_l = np.asarray(ev_l)[:n]
    if not (n_init <= n <= max(budget, n_init)) or not (
            len(u) == len(acc) == len(feas) == len(trace) == n == len(ev_l)):
        faults += 1
        n = min(n, len(u), len(acc), len(feas), len(trace), len(ev_l))
    if np.any((ev_l[:n] < 1) | (ev_l[:n] > arch.L)):
        faults += 1
    run, best = [], -math.inf
    for i in range(n):
        if feas[i]:
            best = max(best, u[i])
        run.append(best if math.isfinite(best) else 0.0)
    if n and not np.array_equal(np.asarray(run, np.float32),
                                trace[:n].astype(np.float32)):
        faults += 1
    has_best = result.best_a is not None
    if has_best != bool(np.any(feas[:n])):
        faults += 1
    elif has_best and np.float32(result.best_utility) != np.float32(best):
        faults += 1

    base = arch.base
    acc_trunc = np.floor(base / QUANTUM + 1e-9) * QUANTUM
    eval_gap = 0.0
    for i in range(n):
        l = int(np.clip(ev_l[i], 1, arch.L))
        if feas[i]:
            band = arch.band(l, g)
            if band is None:
                gap = 1.0
            else:
                lo, hi = band
                gap = max(0.0, u[i] - hi, lo - u[i]) / base
                gap += abs(acc[i] - arch.full_value(l)[1]) / base
        else:
            gap = min(max(abs(u[i]), abs(acc[i])),
                      max(abs(u[i] - base), abs(acc[i] - acc_trunc))) / base
        eval_gap = max(eval_gap, gap)

    answer_gap, regret = 0.0, None
    opt = arch.optimum(g)
    if has_best:
        layers, p = arch.denormalize(result.best_a)
        gaps = []
        for l in layers:
            ur, ar, fr = arch.utility(l, p, g, tol=TOL)
            gaps.append((abs(result.best_utility - ur)
                         + abs(result.best_accuracy - ar)) / base
                        + (0.0 if fr else 1.0))
        answer_gap = min(gaps)
        regret = (opt - result.best_utility) / base
    return dict(ledger_faults=faults, eval_gap=float(eval_gap),
                answer_gap=float(answer_gap),
                regret=None if regret is None else float(regret),
                unanswered=int(not has_best and math.isfinite(opt)))
