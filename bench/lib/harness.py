"""One benchmark run of the streaming split planner.

A run builds the planner's server, ``StreamingBayesSplitEdge``, as a
deployment configures it (default ``EngineConfig``: warm GP refits, no
prior bank), warms it up on the cell's own traffic from other seeds,
serves the cell's traffic for the measured window, holds every solve of
the window to the float64 reference, and prints one JSON line.

Open-loop traffic arrives on its own schedule (``arrivals=``); a
request's latency is its emit time minus its due time, both on the
server's clock. Backlogged traffic is an order-driven feed that the
server pulls as lanes free, one flush of look-ahead ahead. In both, the
feed goes on after the window closes until every request of the window
(due in it, or pulled in it) has emitted, or until ``wait_s`` has passed
and the rest count as missing; then the run stops serving. The server is
never left to drain: a drain shrinks the lane pools and builds programs
of sizes that no steady window uses.
"""
from __future__ import annotations

import gc
import math
import shutil
import signal
import sys
import tempfile
import time
from typing import Callable, Dict, List

import numpy as np

from bench.lib import latency, reference, trace_reduce, traffic as tr
from bench.lib.compile_counter import CompileCounter
from bench.lib.spec import Spec

WAIT_S = 60.0          # how long past the window an answer is awaited
WARM_PASSES = 2
UNSCHEDULED = 1e6      # arrival time of a warm-up burst not yet released
TRACE_S = 10.0         # the profiled part of a traced window


class _Deadline(Exception):
    pass


def _on_alarm(signum, frame):
    raise _Deadline()


class _Feed:
    """Decodes requests into the planner's ``Scenario`` as the server
    pulls them, and notes when each was pulled."""

    def __init__(self, requests, decode: Callable):
        self.requests = requests
        self.decode = decode
        self.pulled: List[tr.Request] = []
        self.pulled_at: List[float] = []

    def __iter__(self):
        for r in self.requests:
            self.pulled.append(r)
            self.pulled_at.append(time.monotonic())
            yield self.decode(*r)


class _Probe:
    """Spans around the server's calls into its lane pools: each
    ``stream_phase`` dispatch from launch to the readback of its
    ``collect``, with the pool's lane count and the loop iterations it
    ran. Times as ``stream_stats()["lane_log"]`` takes them, but per
    window: the server's own stats exist only once ``serve()`` has
    drained. These are private attributes of the server: one that has
    no such pools is refused, rather than leaving the span metrics
    silent."""

    def __init__(self, eng):
        self.rows = []                 # (start, end, lanes, iterations)
        pools = getattr(eng, "_pools", None)
        if not pools or not all(callable(getattr(p, "dispatch", None))
                                and callable(getattr(p, "collect", None))
                                for p in pools):
            raise RuntimeError("the server has no lane pools with dispatch "
                               "and collect to time")
        for pool in pools:
            self._wrap(pool)

    def _wrap(self, pool):
        dispatch, collect = pool.dispatch, pool.collect
        open_ = []

        def timed_dispatch(*a, **k):
            t = time.monotonic()
            entry = dispatch(*a, **k)
            if entry is not None:
                open_.append((t, entry["lanes"]))
            return entry

        def timed_collect(*a, **k):
            out = collect(*a, **k)
            if open_:
                t, lanes = open_.pop()
                self.rows.append((t, time.monotonic(), lanes, out[2]))
            return out

        pool.dispatch, pool.collect = timed_dispatch, timed_collect


class _Tracer:
    """Profiles ``[start, start + length)`` seconds of the serving clock
    with the host's Python tracer off. Starting the profiler and writing
    its trace hold up the serve loop; that time does not count against
    the wait for due answers."""

    def __init__(self, tdir: str, start: float, length: float):
        self.tdir, self.start, self.end = tdir, start, start + length
        self.ann = None
        self.done = False

    def poll(self, now: float) -> None:
        if self.done:
            return
        if self.ann is None and now >= self.start:
            self._paused(self._begin)
        elif self.ann is not None and now >= self.end:
            self._paused(self.close)

    def _begin(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.tdir, profiler_options=opts)
        self.ann = jax.profiler.TraceAnnotation(trace_reduce.WINDOW)
        self.ann.__enter__()

    def close(self):
        import jax
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
            jax.profiler.stop_trace()
        self.done = True

    @staticmethod
    def _paused(step):
        left = signal.getitimer(signal.ITIMER_REAL)[0]
        signal.setitimer(signal.ITIMER_REAL, 0)
        step()
        if left > 0:
            signal.setitimer(signal.ITIMER_REAL, left)


class Bench:
    """A cell's configuration, traffic and reference, and the server
    built from them."""

    def __init__(self, spec: Spec, cell: dict, log=None):
        self.spec, self.cell = spec, cell
        self.cfg = spec.config(cell["config"])
        self.traffic = spec.traffic(cell["traffic"])
        req = self.cfg["requests"]
        self.archs = reference.load_archs(spec.archs_dir, req["archs"])
        self.l_pad = max(a.L for a in self.archs.values())
        self.budget_max = max(req["budgets"])
        self.n_init = self.cfg["n_init"]
        self.lanes = self.cfg["lanes_per_chip"] * self.cfg["chips"]
        self.log = log or (lambda *a: print(*a, file=sys.stderr))
        self.open_loop = self.traffic["loop"] == "open"

    # -- the system under test -----------------------------------------------
    def _decode(self, arch, budget, off, seed):
        from repro.core.batch_bo import scenario_from_request
        return scenario_from_request(arch, gain_offset_db=off, budget=budget,
                                     seed=seed)

    def engine(self, feed, arrivals=None):
        import jax
        from repro.core.engine_config import EngineConfig
        from repro.runtime.stream import StreamingBayesSplitEdge
        pools = self.cfg["pools"]
        devices = jax.devices()[:self.cfg["chips"]] if pools > 1 else None
        return StreamingBayesSplitEdge(
            feed, EngineConfig(), n_lanes=self.lanes, l_pad=self.l_pad,
            budget_max=self.budget_max, n_shards=pools, devices=devices,
            arrivals=arrivals)

    def _requests(self, seed):
        return tr.request_stream(self.cfg["requests"], self.cfg["channel"],
                                 seed)

    def _feed(self, seed, n):
        """The cell's traffic: the feed and, open-loop, the arrival
        times of its first ``n`` requests (the feed then holds ``n``)."""
        stream = self._requests(seed)
        if not self.open_loop:
            return _Feed(stream, self._decode), None
        times = tr.arrivals(self.traffic, n, seed)
        return _Feed(tr.take(stream, n), self._decode), list(times)

    # -- set-up --------------------------------------------------------------
    def warm_up(self, seed: int) -> CompileCounter:
        """Build every program the cell's traffic uses, then serve
        ``WARM_PASSES`` backlogged passes of the cell's own request mix
        from other seeds, which fill every lane and build the dispatch
        programs of each dataset bucket. A fixed amount of warm-up keeps
        set-up steady; the programs each step built are logged, and the
        last pass should build none.

        Every admission size builds programs of its own (the eager
        scatters of the lane state; the readback builds one fetch program
        per pool width whatever the count of lanes retiring), so the
        warm-up first admits a burst of each size up to the pool width,
        of requests whose budget the init design spends: each burst
        retires as a whole on admission."""
        with CompileCounter() as cc:
            width = self.lanes // self.cfg["pools"]
            spread = self.cfg["pools"]       # admissions spread over pools
            self._sweep([1] + list(range(1, width + 1)), spread)
            self.log(f"warm-up sweep: {cc.programs} programs built")
            for p in range(WARM_PASSES):
                before = cc.programs
                feed = _Feed(self._requests(seed + 1 + p), self._decode)
                for j, _ in enumerate(self.engine(feed).serve()):
                    if j + 1 >= 2 * self.lanes:
                        break
                self.log(f"warm-up pass {p}: {cc.programs - before} "
                         f"programs built")
        return cc

    def _sweep(self, sizes, spread):
        """Bursts of ``k * spread`` requests for each ``k`` of ``sizes``
        (the first one starts the pool), each arriving only once the
        burst before it has been answered in full, so that no two are
        admitted together however long a burst takes to build."""
        names = sorted(self.archs)
        reqs, bursts = [], []
        for k in sizes:
            bursts.append(range(len(reqs), len(reqs) + k * spread))
            reqs += [(names[j % len(names)], self.n_init, 0.0, j)
                     for j in range(k * spread)]
        times = [UNSCHEDULED] * len(reqs)
        for i in bursts[0]:
            times[i] = 0.0
        eng = self.engine(_Feed(reqs, self._decode), times)
        b, left = 0, len(bursts[0])
        for _ in eng.serve():
            left -= 1
            if left == 0 and b + 1 < len(bursts):
                b += 1
                for i in bursts[b]:
                    eng.arrivals[i] = 0.0        # due now
                left = len(bursts[b])

    # -- the measured window -------------------------------------------------
    def window(self, seed: int, seconds: float, trace: bool = False,
               wait_s: float = WAIT_S) -> dict:
        """Serve the cell's traffic for ``seconds``; returns the run's
        record: what was due and emitted, the dispatch spans and the
        reduced trace."""
        if self.open_loop:
            rate = self.traffic["rate_hz"]
            n = int(math.ceil(rate * (seconds + wait_s))) + 64
            feed, times = self._feed(seed, n)
            due = {i: t for i, t in enumerate(times) if t < seconds}
        else:
            feed, times = self._feed(seed, 0)
            due = None
        eng = self.engine(feed, times)
        probe = _Probe(eng)
        got: Dict[int, object] = {}
        emit_s: Dict[int, float] = {}
        dup = 0
        tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        tracer = (_Tracer(tdir, seconds / 3, min(TRACE_S, seconds / 3))
                  if trace else None)
        timed_out = False
        in_window = None
        depth = []                    # (serving clock, requests queued)
        old = signal.signal(signal.SIGALRM, _on_alarm)
        with CompileCounter() as cc:
            t_serve = time.monotonic()
            signal.setitimer(signal.ITIMER_REAL, seconds + wait_s)
            remaining = None if due is None else len(due)
            try:
                for res in eng.serve():
                    i = res.index
                    if i in emit_s:
                        dup += 1
                        continue
                    emit_s[i] = res.emit_s
                    if not res.degraded:
                        got[i] = res
                    if remaining is not None and i in due:
                        remaining -= 1
                    now = time.monotonic() - t_serve
                    if now < seconds:
                        depth.append((now, len(eng._pending)))
                    if tracer is not None:
                        tracer.poll(now)
                    if in_window is None and now >= seconds:
                        in_window = cc.programs
                        if due is None:       # backlog: what was pulled
                            due = self._pulled_by(feed, t_serve + seconds)
                            remaining = sum(1 for j in due
                                            if j not in emit_s)
                    if remaining == 0:
                        break
            except _Deadline:
                timed_out = True
            finally:
                signal.setitimer(signal.ITIMER_REAL, 0)
                signal.signal(signal.SIGALRM, old)
                if tracer is not None:
                    tracer.close()
            if in_window is None:
                in_window = cc.programs
        if due is None:               # a server that never emitted again
            due = self._pulled_by(feed, t_serve + seconds)
        reduced = None
        if trace:
            try:
                reduced = trace_reduce.reduce(trace_reduce.load(tdir),
                                              devices=self._trace_devices())
            except (ValueError, FileNotFoundError) as e:
                self.log(f"trace not reduced: {e}")
            finally:
                shutil.rmtree(tdir, ignore_errors=True)
        peak = self._memory_peak()
        pulled = feed.pulled
        del eng, feed
        gc.collect()
        in_win = [i for i in got if emit_s[i] <= seconds]
        spans = [r for r in probe.rows if r[0] - t_serve < seconds]
        return dict(
            seconds=seconds, due=due, got=got, pulled=pulled,
            duplicates=dup, timed_out=timed_out,
            latencies_ms=(latency.latencies_ms(
                due, {i: emit_s[i] for i in got}) if self.open_loop
                else None),
            solves_in_window=len(in_win),
            loop_evals=sum(max(0, got[i].result.n_evals - self.n_init)
                           for i in in_win),
            dispatches=[(e - s, lanes, iters)
                        for s, e, lanes, iters in spans],
            queue_depth=depth,
            trace=reduced, programs_in_window=in_window,
            compiled_in_window=dict(cc.names), memory_peak_bytes=peak)

    @staticmethod
    def _pulled_by(feed, t_end: float) -> dict:
        return {i: 0.0 for i, t in enumerate(feed.pulled_at) if t < t_end}

    def _trace_devices(self):
        return tuple(range(self.cfg["chips"]))

    def _memory_peak(self) -> int:
        import jax
        peak = 0
        for d in jax.devices()[:self.cfg["chips"]]:
            st = d.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
        return peak

    # -- correctness ---------------------------------------------------------
    def check(self, win: dict) -> dict:
        """Compared numbers of the run, each ``(value, limit)``, the count
        of failed solves and, for information, the count of solves left
        unanswered where a feasible point exists and the mean regret.
        The unanswered share holds the loop body (GP refit, acquisition,
        refinement) to finding a feasible answer where one exists; the
        other numbers hold every value it reports to the reference."""
        lim = self.cfg["limits"]
        missing = sum(1 for i in win["due"] if i not in win["got"])
        faults = win["duplicates"]
        eval_gap = answer_gap = 0.0
        regrets, failed, unanswered = [], 0, 0
        for i in win["due"]:
            res = win["got"].get(i)
            if res is None:
                continue
            arch, budget, off, _ = win["pulled"][i]
            c = reference.check_solve(self.archs[arch], off, budget,
                                      self.n_init, res.result,
                                      res.raw["ev_l"])
            faults += c["ledger_faults"]
            eval_gap = max(eval_gap, c["eval_gap"])
            answer_gap = max(answer_gap, c["answer_gap"])
            if c["regret"] is not None:
                regrets.append(c["regret"])
            unanswered += c["unanswered"]
            failed += int(c["ledger_faults"] > 0
                          or c["eval_gap"] > lim["eval_gap"]
                          or c["answer_gap"] > lim["answer_gap"])
        unanswered_share = unanswered / max(1, len(win["due"]))
        checks = dict(missing=(missing, 0), ledger_faults=(faults, 0),
                      eval_gap=(eval_gap, lim["eval_gap"]),
                      answer_gap=(answer_gap, lim["answer_gap"]),
                      unanswered_share=(unanswered_share,
                                        lim["unanswered_share"]))
        return dict(checks=checks, failed=missing + failed,
                    unanswered=unanswered,
                    regret_mean=(float(np.mean(regrets)) if regrets
                                 else None))


def queue_summary(depth, seconds: float) -> str:
    """The deepest admission queue of the window, overall and in each
    third of it: a load the server sustains keeps the last third's
    depth no deeper than the first's."""
    thirds = [max((d for t, d in depth if k * seconds / 3 <= t
                   < (k + 1) * seconds / 3), default=0) for k in range(3)]
    return (f"queue_depth_max={max(thirds)} "
            f"queue_depth_max_by_third={thirds}")


def result_line(bench: Bench, win: dict, checked: dict, setup_s: float,
                setup_programs: int, trace: bool) -> dict:
    """The run's last line, in the benchmark contract's layout."""
    import jax
    record = dict(win, setup_s=setup_s, setup_programs=setup_programs,
                  n_init=bench.n_init)
    metrics = {}
    for m in bench.spec.metrics(bench.cell, trace):
        v = bench.spec.reader(m["name"])(record)
        if v is not None:
            metrics[m["name"]] = dict(value=v, unit=m["unit"])
    devs = jax.devices()
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs), memory_peak_bytes=win["memory_peak_bytes"])
    out = dict(correct=all(v <= lim for v, lim in checked["checks"].values()),
               attempted=len(win["due"]), failed=int(checked["failed"]),
               metrics=metrics, device=device)
    if trace and win["trace"] is not None:
        t = win["trace"]
        device.update(busy_s=t["busy_s"], window_s=t["window_s"])
        out["breakdown"] = dict(device_ops=t["device_ops"],
                                idle_gaps=t["idle_gaps"])
    out["checks"] = {k: dict(value=float(v) if isinstance(v, float)
                             else int(v), limit=lim)
                     for k, (v, lim) in checked["checks"].items()}
    return out
