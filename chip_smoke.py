#!/usr/bin/env python3
"""Bring-up smoke test of the split planner on the chip.

Drives the serving path once, through the entry points a user calls: a
replayed arrival trace of 64 mixed CNN + LM-decoder split-planning
requests is decoded with ``requests_from_trace`` and served by
``StreamingBayesSplitEdge`` (32 lanes, the default 64x64 candidate grid
and GP buckets up to 64 points). It then checks the answers:

  a. every admitted request emits exactly one result;
  b. the stream gives the same answer as the one-dispatch
     ``WholeRunBayesSplitEdge`` run of the same scenarios (cold fits):
     a feasible point or none, at the same quantized accuracy. How many
     results are also bitwise equal is printed. On the CPU the two are
     bitwise equal; on the TPU the last bits of an f32 GP fit depend on
     the padded dataset shape, and a stream lane fits at its pool's
     bucket, not its own, so a trajectory may drift in its last bits;
  c. every reported answer is feasible under the float64 host cost
     oracle, whose quantized accuracy equals the reported one;
  d. the quickstart problem (VGG19, budget 20, seed 0) reaches its
     87.5% optimum through the whole-run engine.

It also reports how many answers match the host-loop reference
(``core/bo.py``); that count is informational, since device f32 matmuls
may round differently from the CPU.

``--chips 4`` runs only the multi-device paths and what they are
compared with: the same feed through four per-device lane pools, and
the ``shard_map`` whole run over a 4-device scenario mesh, each held to
the same answer as the one-device whole run in the same process, with
the bitwise count printed as in check b.

The script refuses to run without a TPU; there is no CPU fallback. Its
last line is one JSON object naming the device the run used.

  python chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import (Scenario, WholeRunBayesSplitEdge,  # noqa: E402
                        default_vgg19_problem)
from repro.core.bo import BayesSplitEdge  # noqa: E402
from repro.core.engine_config import EngineConfig  # noqa: E402
from repro.distributed.sharding import scenario_mesh  # noqa: E402
from repro.launch.compile_cache import place_compile_cache  # noqa: E402
from repro.runtime.stream import (StreamingBayesSplitEdge,  # noqa: E402
                                  requests_from_trace)
from repro.wireless.traces import MIXED_TRACE_ARCHS, arrival_trace  # noqa: E402

QUICKSTART_OPTIMUM = 87.5      # Table 1: split layer 7, 0.38 W


class CompileCounter:
    """Counts XLA executables built while it is entered: every backend
    compile request, and how many of those the persistent compilation
    cache answered."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.compiled = 0
        self.cache_hits = 0
        self.seconds = 0.0

    def _on_duration(self, event, duration, **_):
        if event == self._COMPILE:
            self.compiled += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == self._HIT:
            self.cache_hits += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def smoke_trace(n_requests: int, seed: int) -> dict:
    """The replayed mMobile arrival trace over the CNN + LM request mix
    (VGG19 L=37, ResNet101 L=36 and LM decoders up to L=61)."""
    return arrival_trace("replay", n=n_requests, seed=seed,
                         budgets=(6, 10, 14, 20), archs=MIXED_TRACE_ARCHS)


def bitwise_equal(a, b) -> bool:
    """Two ``BOResult``s are the same run: same evaluations, trace and
    answer, bit for bit."""
    same_a = ((a.best_a is None and b.best_a is None)
              or (a.best_a is not None and b.best_a is not None
                  and np.array_equal(a.best_a, b.best_a)))
    return (same_a and a.n_evals == b.n_evals
            and a.utilities == b.utilities
            and a.incumbent_trace == b.incumbent_trace
            and a.feasible == b.feasible
            and a.best_accuracy == b.best_accuracy)


def same_answer(a, b) -> bool:
    """The same reported answer: a feasible point or none, at the same
    quantized accuracy."""
    return ((a.best_a is None) == (b.best_a is None)
            and a.best_accuracy == b.best_accuracy)


def _serve(feed, n_lanes: int, **kw):
    """One cold serve of ``feed``: (results in completion order, engine,
    wall seconds, CompileCounter)."""
    with CompileCounter() as cc:
        t0 = time.perf_counter()
        eng = StreamingBayesSplitEdge(feed, EngineConfig(warm_start=False),
                                      n_lanes=n_lanes, **kw)
        results = list(eng.serve())
        wall = time.perf_counter() - t0
    return results, eng, wall, cc


def _compare(name: str, got: dict, ref, log) -> tuple:
    """Hold ``got`` (arrival index -> result) to the same answers as the
    reference ``ref``; log how many are also bitwise equal, and where
    either comparison fails. Returns (every answer the same, number
    bitwise equal)."""
    diff = sorted(i for i, r in got.items() if not bitwise_equal(r, ref[i]))
    wrong = sorted(i for i, r in got.items() if not same_answer(r, ref[i]))
    n = len(ref)
    log(f"{name}: bitwise {n - len(diff)}/{n} (differ at {diff}), "
        f"same answer {n - len(wrong)}/{n} (differ at {wrong})")
    return len(got) == n and not wrong, n - len(diff)


def _exactly_once(results, n: int) -> bool:
    idx = sorted(r.index for r in results)
    return idx == list(range(n)) and not any(r.degraded for r in results)


def serve_phase(n_requests: int = 64, n_lanes: int = 32, seed: int = 0,
                log=print) -> dict:
    """The one-device main path and checks a-d. Returns the report; its
    ``checks`` maps each check to whether it passed."""
    trace = smoke_trace(n_requests, seed)
    cold = EngineConfig(warm_start=False, compact=False)

    results, _, setup_s, cc = _serve(requests_from_trace(trace), n_lanes)
    log(f"setup_s={setup_s:.3f} programs_compiled={cc.compiled} "
        f"persistent_cache_hits={cc.cache_hits} "
        f"backend_compile_s={cc.seconds:.3f}")
    again, _, serve_s, cc2 = _serve(requests_from_trace(trace), n_lanes)
    log(f"serve_s={serve_s:.6f} compiles_in_window={cc2.compiled} "
        f"solves_completed={len(again)}")

    # a. exactly one result per admitted request (both serves)
    ok_a = (_exactly_once(results, n_requests)
            and _exactly_once(again, n_requests))

    # b. the served answers are the one-dispatch whole run's (cold fits)
    t0 = time.perf_counter()
    ref = WholeRunBayesSplitEdge(requests_from_trace(trace), cold).run()
    log(f"wholerun_s={time.perf_counter() - t0:.3f} (compile included)")
    by_idx = {r.index: r.result for r in results}
    ok_b, n_bitwise = _compare("stream_vs_wholerun", by_idx, ref, log)

    # c. host float64 oracle agrees with every reported answer
    bad_c = []
    n_feasible = 0
    for r in results:
        a, pb = r.result.best_a, r.scenario.problem
        if a is None:
            continue
        n_feasible += 1
        acc = pb._accuracy(*pb.denormalize(a))[1]
        if not pb.feasible(a) or acc != r.result.best_accuracy:
            e, tau = pb.constraint_values(a)
            b = pb.cm.budgets
            bad_c.append(r.index)
            log(f"check c: request {r.index} a={a.tolist()} "
                f"E={e!r} (max {b.e_max_j!r}, margin {b.e_max_j - e!r}) "
                f"tau={tau!r} (max {b.tau_max_s!r}, "
                f"margin {b.tau_max_s - tau!r}) "
                f"acc={acc!r} reported={r.result.best_accuracy!r}")
    log(f"answers={n_feasible} no_feasible_point={n_requests - n_feasible}")

    # d. the quickstart problem reaches its optimum on the whole-run engine
    qs = WholeRunBayesSplitEdge(
        [Scenario(default_vgg19_problem(), seed=0, budget=20)]).run()[0]
    pb = default_vgg19_problem()
    qs_l, qs_p = (pb.denormalize(qs.best_a) if qs.best_a is not None
                  else (None, None))
    log(f"quickstart: accuracy={qs.best_accuracy!r} split={qs_l} "
        f"power_w={qs_p!r} evals={qs.n_evals}")

    # host-loop reference: informational, f32 device matmuls may round
    # differently from the CPU
    t0 = time.perf_counter()
    host = [BayesSplitEdge(sc.problem, budget=sc.budget).run(seed=sc.seed)
            for sc in requests_from_trace(trace)]
    matched = sum(by_idx[i].best_accuracy == h.best_accuracy
                  for i, h in enumerate(host))
    log(f"host_reference_matched={matched}/{n_requests} "
        f"host_reference_s={time.perf_counter() - t0:.3f}")

    checks = dict(
        a_exactly_once=ok_a,
        b_same_answer_wholerun=ok_b,
        c_host_oracle_feasible=not bad_c,
        d_quickstart_optimum=qs.best_accuracy >= QUICKSTART_OPTIMUM - 1e-9)
    log("checks: " + " ".join(f"{k}={v}" for k, v in checks.items()))
    return dict(checks=checks, setup_s=setup_s, programs_compiled=cc.compiled,
                cache_hits=cc.cache_hits, serve_s=serve_s,
                compiles_in_window=cc2.compiled, solves=len(again),
                answers=n_feasible, host_reference_matched=matched,
                bitwise_wholerun=n_bitwise)


def _pool_devices(eng) -> list:
    """The devices each lane pool's state actually lives on, read from
    the arrays themselves."""
    return [frozenset(d for leaf in jax.tree.leaves(p.state)
                      for d in leaf.devices())
            for p in eng._pools if p.state is not None]


def sharded_phase(n_devices: int = 4, n_requests: int = 64,
                  n_lanes: int = 32, seed: int = 0, log=print) -> dict:
    """The multi-device paths, each held to the answers of the one-device
    whole run (cold fits) in the same process, as in check b: per-device
    lane pools behind one stream server, and the ``shard_map`` scenario
    mesh."""
    trace = smoke_trace(n_requests, seed)
    cold = EngineConfig(warm_start=False, compact=False)
    devices = jax.devices()[:n_devices]

    t0 = time.perf_counter()
    ref = WholeRunBayesSplitEdge(requests_from_trace(trace), cold).run()
    log(f"one_device_wholerun_s={time.perf_counter() - t0:.3f}")

    # per-device pools: read the placement after the first round and
    # again at the end
    with CompileCounter() as cc:
        t0 = time.perf_counter()
        eng = StreamingBayesSplitEdge(
            requests_from_trace(trace), EngineConfig(warm_start=False),
            n_lanes=n_lanes, n_shards=n_devices, devices=devices)
        results, placed = [], None
        for r in eng.serve():
            results.append(r)
            if placed is None:
                placed = _pool_devices(eng)
        wall = time.perf_counter() - t0
    placed_end = _pool_devices(eng)
    distinct = (len(placed) == n_devices
                and all(len(d) == 1 for d in placed)
                and len(set().union(*placed)) == n_devices
                and placed_end == placed)
    log(f"sharded_stream_s={wall:.3f} programs_compiled={cc.compiled} "
        f"pool_devices={[sorted(d.id for d in s) for s in placed]}")
    ok_stream, _ = _compare("sharded_stream_vs_one_device",
                            {r.index: r.result for r in results}, ref,
                            log)

    t0 = time.perf_counter()
    mesh_res = WholeRunBayesSplitEdge(requests_from_trace(trace), cold,
                                      mesh=scenario_mesh(n_devices)).run()
    log(f"mesh_wholerun_s={time.perf_counter() - t0:.3f}")
    ok_mesh, _ = _compare("mesh_wholerun_vs_one_device",
                          dict(enumerate(mesh_res)), ref, log)

    checks = dict(
        pools_on_distinct_devices=distinct,
        exactly_once=_exactly_once(results, n_requests),
        sharded_stream_same_answer=ok_stream,
        mesh_wholerun_same_answer=ok_mesh)
    log("checks: " + " ".join(f"{k}={v}" for k, v in checks.items()))
    return dict(checks=checks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-device paths")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the arrival trace")
    args = ap.parse_args(argv)

    devs = jax.devices()
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs))
    print(f"device: platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found; this test runs only on the chip",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1

    print(f"compile_cache: {place_compile_cache()}", flush=True)
    log = lambda s: print(s, flush=True)  # noqa: E731
    t0 = time.perf_counter()
    if args.chips > 1:
        report = sharded_phase(args.chips, seed=args.seed, log=log)
    else:
        report = serve_phase(seed=args.seed, log=log)
    log(f"total_s={time.perf_counter() - t0:.3f}")
    failed = [k for k, ok in report["checks"].items() if not ok]
    if failed:
        print(f"chip_smoke: failed checks {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
