"""Traffic generation: request streams and arrival times from a seed.

Requests are (architecture, evaluation budget, channel offset, init
seed). Every block of requests holds the same multiset of architecture
and budget pairs and of channel frames, and every block of arrival gaps
the same multiset of gaps; the seed only orders them and draws the init
seeds. Runs with different seeds then carry the same work in another
order, which keeps the spread between runs down to what the system
itself adds.

``synth_mmobile_trace`` is a copy of the planner's mMobile-like channel
synthesizer, and the arrival processes keep the shape of its Poisson and
on/off burst generators, with each exponential or uniform draw replaced
by a seed-ordered multiset of its quantiles.
"""
from __future__ import annotations

import itertools
import math
from typing import Iterator, List, Tuple

import numpy as np

Request = Tuple[str, int, float, int]     # arch, budget, gain offset dB, seed


def synth_mmobile_trace(seed: int = 0, n_frames: int = 450,
                        mean_db: float = -102.64,
                        fading_std_db: float = 2.5,
                        blockage_depth_db: float = 9.0,
                        blockage_rate: float = 0.08,
                        blockage_len: int = 12) -> np.ndarray:
    """Per-frame channel gain |h|^2 in dB: AR(1) shadowing, log-normal
    fast fading and blockage events, as in the mMobile (mmNets'20)
    outdoor 30 m link."""
    rng = np.random.default_rng(seed)
    shadow = np.zeros(n_frames)
    rho, sig = 0.97, 1.0
    for t in range(1, n_frames):
        shadow[t] = (rho * shadow[t - 1]
                     + sig * np.sqrt(1 - rho ** 2) * rng.standard_normal())
    fast = fading_std_db * rng.standard_normal(n_frames)
    block = np.zeros(n_frames)
    t = 0
    while t < n_frames:
        if rng.random() < blockage_rate:
            depth = blockage_depth_db * (0.7 + 0.6 * rng.random())
            block[t:t + blockage_len] = -depth
            t += blockage_len
        else:
            t += 1
    return mean_db + shadow + fast + block


def channel_offsets(channel: dict) -> np.ndarray:
    """Per-frame gain offsets (dB) from the calibrated operating point:
    each frame's gain minus the trace mean, as the planner's arrival
    traces define a request's channel."""
    g = synth_mmobile_trace(**channel)
    return g - g.mean()


def request_stream(requests: dict, channel: dict, seed: int
                   ) -> Iterator[Request]:
    """Endless request stream. Each block pairs every architecture with
    every budget equally often and walks a seed-ordered permutation of
    the channel frames."""
    pairs = [(a, int(b)) for b in requests["budgets"]
             for a in requests["archs"]]
    offs = channel_offsets(channel)
    block = len(pairs) * max(1, math.ceil(32 / len(pairs)))
    rng = np.random.default_rng([int(seed), 7])
    frames = rng.permutation(len(offs))
    k = 0
    while True:
        for j in rng.permutation(block):
            a, b = pairs[j % len(pairs)]
            yield (a, b, float(offs[frames[k % len(offs)]]),
                   int(rng.integers(0, 2 ** 31 - 1)))
            k += 1


def _quantile_gaps(n: int, mean: float) -> np.ndarray:
    """The n mid-quantiles of an exponential distribution of this mean."""
    q = (np.arange(n) + 0.5) / n
    return -mean * np.log1p(-q)


def poisson_arrivals(n: int, rate_hz: float, seed: int,
                     block: int = 64) -> np.ndarray:
    """Poisson arrivals at ``rate_hz``: each block of ``block`` arrivals
    spans the same time, its exponential gaps in seed order."""
    rng = np.random.default_rng([int(seed), 11])
    base = _quantile_gaps(block, 1.0 / rate_hz)
    gaps = np.concatenate([rng.permutation(base)
                           for _ in range(math.ceil(n / block))])[:n]
    return np.cumsum(gaps)


def bursty_arrivals(n: int, rate_hz: float, burst_len: int,
                    burst_rate_hz: float, seed: int,
                    block: int = 16) -> np.ndarray:
    """On/off bursts: ``burst_len`` arrivals at ``burst_rate_hz``, then an
    idle gap of ``idle_s * U`` with ``U`` uniform on [0.5, 1.5), where
    ``idle_s`` is set so that the mean rate is ``rate_hz``. Each block of
    ``block`` bursts spans the same time."""
    in_burst = burst_len / burst_rate_hz
    idle_s = burst_len / rate_hz - in_burst
    if idle_s <= 0:
        raise ValueError(f"a mean rate of {rate_hz}/s leaves no idle time "
                         f"between bursts of {burst_len} at "
                         f"{burst_rate_hz}/s")
    rng = np.random.default_rng([int(seed), 13])
    within = _quantile_gaps(burst_len, 1.0 / burst_rate_hz)
    idles = idle_s * (0.5 + (np.arange(block) + 0.5) / block)
    out, t = [], 0.0
    for _ in range(math.ceil(n / burst_len / block)):
        for idle in rng.permutation(idles):
            for g in rng.permutation(within):
                t += g
                out.append(t)
            t += idle
    return np.asarray(out[:n])


def arrivals(traffic: dict, n: int, seed: int) -> np.ndarray:
    """Arrival times (s) of an open-loop traffic mix."""
    kind = traffic["arrivals"]
    if kind == "poisson":
        return poisson_arrivals(n, traffic["rate_hz"], seed)
    if kind == "bursty":
        return bursty_arrivals(n, traffic["rate_hz"], traffic["burst_len"],
                               traffic["burst_rate_hz"], seed)
    raise ValueError(f"unknown arrival process {kind!r}")


def take(stream: Iterator[Request], n: int) -> List[Request]:
    return list(itertools.islice(stream, n))
