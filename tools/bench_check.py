#!/usr/bin/env python
"""CI gate for the BO engine: runs benchmarks/bench_engine.py in a small
smoke configuration — under 8 forced host-platform devices so the
scenario-sharded path is exercised — and fails (nonzero exit) if any
gate breaks:

  * batched_not_slower_than_sequential — the batched engine beats the
    sequential jit-hoisted loop;
  * wholerun_not_slower_than_batched — the whole-run single-dispatch
    engine beats the batched (PR 1) engine;
  * zero_rejits_after_warmup — the BO iteration loop does not re-jit
    after warmup (per-iteration compile count / trace-cache size flat);
  * wholerun_zero_post_warmup_compiles — the whole-run engine compiles
    nothing on its timed (post-warmup) runs;
  * batched_matches_sequential / wholerun_matches_batched — the engines
    agree on per-scenario accuracies;
  * sharded_matches_unsharded — the sharded whole run matches the
    unsharded one (eval counts and accuracies equal, incumbent traces
    within the studied tolerance — bitwise equality is not a contract
    across shard sizes);
  * mixed_matches_per_arch — a mixed VGG19+ResNet101 (max-L padded)
    batch through both engines matches per-architecture runs
    scenario-for-scenario;
  * compacted_matches_uncompacted — on the heterogeneous-budget batch
    (budgets 6..20, VGG19+ResNet101), wholerun-with-lane-compaction
    matches the one-dispatch wholerun scenario-for-scenario (bitwise
    for cold fits, within the studied trace tolerance warm);
  * compaction_not_slower — wholerun-with-compaction is not slower than
    the uncompacted wholerun on that batch (<= 1.05x);
  * packing_result_invariant — architecture-aware lane packing
    (in-batch sort and per-shard packed programs) is a pure permutation
    of results (bitwise on cold runs);
  * streaming_matches_offline — a replayed request feed through the
    streaming admission-queue engine (16 heterogeneous requests over 8
    lanes) is bitwise equal (cold fits) / within the studied tolerance
    (warm) to the same scenarios run as one offline batch;
  * streaming_throughput — the server's arrivals/s stays within 1.15x
    of the offline batched engine's scenarios/s on that workload (the
    ratio against the stronger wholerun-compacted path is recorded for
    tracking);
  * chaos_replay_match — recovery from every injected fault class
    (process kill at three dispatch rounds + checkpoint/resume,
    NaN-poisoned lane + quarantine requeue, lane-pool loss +
    re-admission onto the survivor) replay-matches the fault-free run
    (bitwise for cold fits, within the studied trace tolerance warm;
    post-dedup for the kill/resume merge), and recovery costs at most
    1.25x the fault-free wall clock (min over >=3 interleaved repeats;
    the deterministic computed-work ratio — lane-slots, the
    bounded-re-execution audit — is recorded alongside);
  * deadline_hit_rate — on a deadlined bursty trace, EDF admission +
    hopeless shedding does not lose to FIFO on deadline hit rate (the
    A/B is wall-clock paced, so it retries under transient load: best
    of <=3 attempts, count recorded), and neither schedule wedges:
    every admitted request emits exactly one (possibly degraded)
    result;
  * quarantine_never_wedges — a lane driven past every repair rung
    retires with a degraded best-effort answer instead of wedging the
    server (every request still emits exactly once);
  * elastic_matches_fixed — an elastic server (grow/shrink between
    dispatches, hysteresis controller) replay-matches the fixed-width
    server on the same feed (bitwise cold, within the studied trace
    tolerance warm) while actually resizing (n_grows >= 1);
  * overload_bounded_queue — under a bursty trace at 4x nominal load
    the admission queue never exceeds max_pending and every request
    still emits exactly one (possibly degraded) result;
  * failover_routing_hit_rate — under a flapped then slowed pool,
    score routing's deadline hit rate does not lose to round-robin
    (wall-clock paced: best of <=3 attempts like deadline_hit_rate)
    and both schedules emit exactly once;
  * warmprior_matches_cold_off — a never-hitting (frozen empty) prior
    bank reproduces the bank=None run bitwise on every surrogate
    family (the cold-fallback contract of the transfer-learned bank);
  * warmprior_fewer_evals — on the held-out slice of an mMobile replay
    trace, a bank warmed on the training slice reaches the cold run's
    final best utility in strictly fewer evaluations on at least one
    held-out workload and never more on any (and the warm incumbent is
    never worse), per surrogate family;
  * fleet_matches_single_host — a zero-fault 2-worker fleet
    (runtime/fleet.py over the simulated transport) bitwise-matches
    the single-process streaming engine on the canonical
    heterogeneous batch (cold fits: fleet placement is pure
    re-scheduling);
  * fleet_lossy_exactly_once — under a lossy network (5% drop +
    duplication + reordering + one partition/heal cycle) over a
    bursty deadlined trace, every request emits exactly one
    post-dedup result and the deadline hit rate stays within 0.9x of
    the fault-free fleet on the same trace;
  * lm_matches_per_arch — the mixed CNN+LM batch (VGG19/ResNet101 plus
    the LM decoder mix, L 24..61) is bitwise equal to per-arch runs
    through the wholerun engine, the streaming engine AND the packed
    shards (cold fits);
  * lm_packing_padding_win — on that L=24..61 batch, arch-aware shard
    packing's padding waste is strictly below the global-pad layout
    (the win the packing machinery was built for — ~0 on the CNN-only
    batch where L is 36..37);
  * trend_deadline_hit_rate / trend_streaming_throughput — the two
    serving headline numbers (EDF deadline hit rate, streaming
    arrivals/s) must not regress more than 10% against the median of
    the last 5 bench_history.jsonl records (skipped until the history
    holds 5 comparable records or with --no-history).

The gate outcome is also emitted as ONE machine-readable line::

    BENCH_CHECK_SUMMARY {"<gate>": {"ok": true, ...values...}, ...}

so the CI log shows *which* gate broke and with what numbers, and the
same record is appended to benchmarks/artifacts/bench_history.jsonl
(uploaded as a CI workflow artifact) so the perf trajectory stays
visible across PRs. The exit status is the number of failed gates
(0 == all green).

Usage: PYTHONPATH=src python tools/bench_check.py [--scenarios 4]
       (--devices 0 disables the forced host-device override,
        --no-history skips the bench_history.jsonl append)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scenarios", type=int, default=4)
    ap.add_argument("--budget", type=int, default=16)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--devices", type=int, default=8,
                    help="forced host-platform device count for the "
                         "sharded path (0 disables)")
    ap.add_argument("--history", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="append the gate record to benchmarks/artifacts/"
                         "bench_history.jsonl (--no-history disables)")
    args = ap.parse_args()

    # must run before jax initializes (the first jax import below)
    if args.devices:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()

    from repro.launch.compile_cache import place_compile_cache
    place_compile_cache()

    from benchmarks.bench_engine import run

    # legacy baseline disabled: the gate compares against the current
    # sequential loop, which is the stricter bar
    r = run(n_scenarios=args.scenarios, budget=args.budget,
            repeats=args.repeats, n_legacy=0, save=False)

    gates: dict = {}

    def gate(name: str, ok, **values) -> None:
        gates[name] = dict(ok=bool(ok), **values)

    gate("batched_not_slower_than_sequential",
         r["batched_s"] <= r["sequential_s"],
         batched_s=r["batched_s"], sequential_s=r["sequential_s"])
    gate("wholerun_not_slower_than_batched",
         r["wholerun_s"] <= r["batched_s"],
         wholerun_s=r["wholerun_s"], batched_s=r["batched_s"])
    gate("zero_rejits_after_warmup", r["zero_rejits_after_warmup"],
         per_iteration_compile_counts=r["per_iteration_compile_counts"],
         per_iteration_trace_cache_sizes=(
             r["per_iteration_trace_cache_sizes"]))
    gate("wholerun_zero_post_warmup_compiles",
         r["wholerun_extra_compiles"] == 0,
         extra_compiles=r["wholerun_extra_compiles"])
    gate("batched_matches_sequential",
         r["accuracies"]["sequential"] == r["accuracies"]["batched"],
         accuracies=r["accuracies"])
    gate("wholerun_matches_batched",
         r["accuracies"]["wholerun"] == r["accuracies"]["batched"],
         accuracies=r["accuracies"])
    if r["n_devices"] > 1:
        gate("sharded_matches_unsharded", r["sharded_matches_unsharded"],
             sharded_s=r["sharded_s"], n_devices=r["n_devices"])
    gate("mixed_matches_per_arch", r["mixed_matches_per_arch"],
         **(r["mixed_arch"] or {}))
    # lane compaction + arch-aware packing (heterogeneous-budget batch)
    h = r["hetero"]
    gate("compacted_matches_uncompacted",
         h["compacted_matches_uncompacted"],
         cold_bitwise_match=h["cold_bitwise_match"],
         warm_within_tol=h["warm_within_tol"],
         n_scenarios=h["n_scenarios"],
         budgets=[h["budget_min"], h["budget_max"]])
    gate("compaction_not_slower",
         h["wholerun_compacted_s"] <= 1.05 * h["wholerun_s"],
         wholerun_s=h["wholerun_s"],
         wholerun_compacted_s=h["wholerun_compacted_s"],
         compaction_speedup=h["compaction_speedup"],
         live_occupancy_uncompacted=h["live_occupancy_uncompacted"],
         live_occupancy_compacted=h["live_occupancy_compacted"])
    gate("packing_result_invariant", h["packing_bitwise_match"],
         padding_waste_ratio=h["padding_waste_ratio"],
         padding_waste_ratio_packed=h["padding_waste_ratio_packed"])
    # streaming admission-queue serving engine
    s = r["streaming"]
    gate("streaming_matches_offline", s["matches_offline"],
         cold_bitwise_match=s["cold_bitwise_match"],
         warm_within_tol=s["warm_within_tol"],
         n_requests=s["n_requests"], n_lanes=s["n_lanes"])
    gate("streaming_throughput",
         s["streaming_s"] <= 1.15 * s["batched_s"],
         streaming_s=s["streaming_s"], batched_s=s["batched_s"],
         arrivals_per_s=s["arrivals_per_s"],
         slowdown_vs_batched=s["slowdown_vs_batched"],
         slowdown_vs_wholerun=s["slowdown_vs_wholerun"],
         occupancy_mean=s["occupancy_mean"],
         queue_depth_max=s["queue_depth_max"])
    # crash-safe serving: fault-injected recovery + deadline admission
    c = r["chaos"]
    gate("chaos_replay_match",
         r["chaos_replay_match"] and c["recovery_overhead"] <= 1.25,
         kill_rounds=c["kill_rounds"], kill_matches=c["kill_matches"],
         poison_cold_bitwise=c["poison_cold_bitwise"],
         poison_warm_within_tol=c["poison_warm_within_tol"],
         pool_drop_match=c["pool_drop_match"],
         recovery_overhead=c["recovery_overhead"],
         recovery_work_overhead=c["recovery_work_overhead"],
         faultfree_s=c["faultfree_s"], recovery_s=c["recovery_s"])
    gate("deadline_hit_rate",
         (c["edf_hit_rate"] >= c["fifo_hit_rate"]
          and c["deadline_exactly_once"]),
         edf_hit_rate=c["edf_hit_rate"], fifo_hit_rate=c["fifo_hit_rate"],
         deadline=c["deadline"])
    gate("quarantine_never_wedges", c["quarantine_no_wedge"],
         n_quarantined=c["n_quarantined"],
         poison_n_requeued=c["poison_n_requeued"])
    # overload tolerance: elastic pools, bounded queue, failover routing
    o = r["overload"]
    gate("elastic_matches_fixed", o["elastic_matches_fixed"],
         elastic_cold_bitwise=o["elastic_cold_bitwise"],
         elastic_warm_within_tol=o["elastic_warm_within_tol"],
         n_grows=o["elastic_n_grows"], n_shrinks=o["elastic_n_shrinks"],
         elastic_overhead=o["elastic_overhead"],
         resize_log=o["elastic_resize_log"])
    gate("overload_bounded_queue",
         o["queue_bounded"] and o["overload_exactly_once"],
         queue_depth_max=o["queue_depth_max"],
         max_pending=o["max_pending"],
         n_overflow_shed=o["n_overflow_shed"],
         overload_hit_rate=o["overload_hit_rate"],
         exactly_once=o["overload_exactly_once"])
    gate("failover_routing_hit_rate",
         (o["routing_hit_rate"] >= o["rr_hit_rate"]
          and o["failover_exactly_once"]),
         routing_hit_rate=o["routing_hit_rate"],
         rr_hit_rate=o["rr_hit_rate"], failover=o["failover"])
    # transfer-learned prior bank: cold-fallback bitwise + the transfer
    # lever on a held-out mMobile replay slice, per surrogate family
    t = r["transfer"]
    gate("warmprior_matches_cold_off", t["matches_cold_off"],
         per_surrogate={k: v["matches_cold_off"]
                        for k, v in t["surrogates"].items()})
    gate("warmprior_fewer_evals",
         t["fewer_evals"] and t["warm_never_worse"],
         warm_never_worse=t["warm_never_worse"],
         per_surrogate={
             k: dict(cold=v["cold_evals_total"],
                     warm=v["warm_evals_total"],
                     strictly_fewer_on=v["strictly_fewer_on"],
                     never_more=v["never_more"],
                     heldout_hit_rate=v["heldout_hit_rate"])
             for k, v in t["surrogates"].items()})

    # fleet front end: multi-host transport parity + lossy exactly-once
    fl = r["fleet"]
    gate("fleet_matches_single_host", r["fleet_matches_single_host"],
         n_workers=fl["n_workers"], n_lanes=fl["n_lanes"],
         fleet_s=fl["fleet_s"], fleet_cycles=fl["fleet_cycles"])
    gate("fleet_lossy_exactly_once", r["fleet_lossy_exactly_once"],
         lossy_exactly_once=fl["lossy_exactly_once"],
         lossy_hit_rate=fl["lossy_hit_rate"],
         faultfree_hit_rate=fl["faultfree_hit_rate"],
         hit_rate_ok=fl["lossy_hit_rate_ok"],
         n_retries=fl["lossy_n_retries"],
         n_dup_results=fl["lossy_n_dup_results"],
         n_degraded=fl["lossy_n_degraded"],
         transport=fl["lossy_transport"])

    # LM-decoder scenarios: mixed CNN+LM parity + the packing payoff
    lm = r["lm"]
    gate("lm_matches_per_arch", r["lm_matches_per_arch"],
         wholerun_bitwise=lm["wholerun_bitwise_match"],
         streaming_bitwise=lm["streaming_bitwise_match"],
         packing_bitwise=lm["packing_bitwise_match"],
         n_scenarios=lm["n_scenarios"], archs=list(lm["archs"]),
         l_values=lm["l_values"])
    gate("lm_packing_padding_win", r["lm_packing_padding_win"],
         padding_waste_ratio=lm["padding_waste_ratio"],
         padding_waste_ratio_packed=lm["padding_waste_ratio_packed"],
         l_min=lm["l_min"], l_max=lm["l_max"],
         wholerun_s=lm["wholerun_s"],
         wholerun_packed_s=lm["wholerun_packed_s"])

    # perf trend: the serving headline numbers must not regress >10%
    # against the median of the last 5 recorded runs. The history is
    # read BEFORE this run's record is appended, so the gate compares
    # against prior runs only; with fewer than 5 comparable records
    # (or --no-history) the trend gates are skipped, not failed.
    hist = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "benchmarks", "artifacts",
                        "bench_history.jsonl")
    prior = []
    if args.history and os.path.exists(hist):
        with open(hist) as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        prior.append(json.loads(line))
                    except ValueError:
                        continue

    def trend(name: str, current: float, key: str) -> None:
        vals = [rec[key] for rec in prior
                if isinstance(rec.get(key), (int, float))][-5:]
        if len(vals) < 5:
            return
        med = sorted(vals)[2]
        gate(name, current >= 0.9 * med, current=current,
             median_of_last_5=med, last_5=vals)

    trend("trend_deadline_hit_rate", c["edf_hit_rate"],
          "chaos_edf_hit_rate")
    trend("trend_streaming_throughput", s["arrivals_per_s"],
          "streaming_arrivals_per_s")

    sharded = ("n/a" if r["sharded_s"] is None
               else f"{r['sharded_s']:.2f}s/{r['n_devices']}dev")
    mixed = r["mixed_arch"]
    print(f"bench_check: {args.scenarios} scenarios, budget {args.budget}: "
          f"sequential {r['sequential_s']:.2f}s, batched {r['batched_s']:.2f}s "
          f"({r['speedup_vs_sequential']}x), wholerun {r['wholerun_s']:.2f}s "
          f"({r['speedup_wholerun_vs_batched']}x vs batched), "
          f"sharded {sharded}, "
          f"mixed-arch {mixed['batched_s']:.2f}s/"
          f"{mixed['n_scenarios']}scen, "
          f"compaction {h['compaction_speedup']}x "
          f"(occupancy {h['live_occupancy_uncompacted']:.2f}->"
          f"{h['live_occupancy_compacted']:.2f}), "
          f"streaming {s['streaming_s']:.2f}s/"
          f"{s['n_requests']}req@{s['n_lanes']}lanes "
          f"({s['arrivals_per_s']:.0f} arr/s), "
          f"chaos replay-match={r['chaos_replay_match']} "
          f"(recovery {c['recovery_overhead']}x, "
          f"edf {c['edf_hit_rate']} vs fifo {c['fifo_hit_rate']}), "
          f"overload elastic-match={o['elastic_matches_fixed']} "
          f"queue {o['queue_depth_max']}/{o['max_pending']} "
          f"routing {o['routing_hit_rate']} vs rr {o['rr_hit_rate']}, "
          f"transfer cold-off={t['matches_cold_off']} "
          f"fewer-evals={t['fewer_evals']}, "
          f"fleet match={r['fleet_matches_single_host']} "
          f"lossy-once={r['fleet_lossy_exactly_once']} "
          f"(hit {fl['lossy_hit_rate']} vs {fl['faultfree_hit_rate']}), "
          f"lm match={r['lm_matches_per_arch']} "
          f"(L {lm['l_min']}..{lm['l_max']}, padding "
          f"{lm['padding_waste_ratio']:.2f}->"
          f"{lm['padding_waste_ratio_packed']:.2f}), "
          f"zero-rejits={r['zero_rejits_after_warmup']}")
    print("BENCH_CHECK_SUMMARY " + json.dumps(gates, sort_keys=True))

    if args.history:
        # one JSONL record per CI run — the cross-PR perf trajectory
        # (uploaded as a workflow artifact by .github/workflows/ci.yml;
        # appended AFTER the trend gates read the prior records)
        os.makedirs(os.path.dirname(hist), exist_ok=True)
        record = dict(
            ts=int(time.time()),
            scenarios=args.scenarios, budget=args.budget,
            sequential_s=r["sequential_s"], batched_s=r["batched_s"],
            wholerun_s=r["wholerun_s"], sharded_s=r["sharded_s"],
            compaction_speedup=h["compaction_speedup"],
            live_occupancy_compacted=h["live_occupancy_compacted"],
            streaming_s=s["streaming_s"],
            streaming_arrivals_per_s=s["arrivals_per_s"],
            streaming_slowdown_vs_wholerun=s["slowdown_vs_wholerun"],
            chaos_recovery_overhead=c["recovery_overhead"],
            chaos_edf_hit_rate=c["edf_hit_rate"],
            chaos_fifo_hit_rate=c["fifo_hit_rate"],
            overload_elastic_overhead=o["elastic_overhead"],
            overload_queue_depth_max=o["queue_depth_max"],
            overload_routing_hit_rate=o["routing_hit_rate"],
            overload_rr_hit_rate=o["rr_hit_rate"],
            transfer_cold_evals_total=sum(
                v["cold_evals_total"] for v in t["surrogates"].values()),
            transfer_warm_evals_total=sum(
                v["warm_evals_total"] for v in t["surrogates"].values()),
            transfer_heldout_hit_rate=round(
                sum(v["heldout_hit_rate"]
                    for v in t["surrogates"].values())
                / max(len(t["surrogates"]), 1), 3),
            fleet_s=fl["fleet_s"],
            fleet_lossy_hit_rate=fl["lossy_hit_rate"],
            fleet_faultfree_hit_rate=fl["faultfree_hit_rate"],
            lm_padding_waste=lm["padding_waste_ratio"],
            lm_padding_waste_packed=lm["padding_waste_ratio_packed"],
            lm_wholerun_s=lm["wholerun_s"],
            lm_packed_s=lm["wholerun_packed_s"],
            gates=gates)
        with open(hist, "a") as f:
            f.write(json.dumps(record, sort_keys=True) + "\n")

    failed = [name for name, g in gates.items() if not g["ok"]]
    for name in failed:
        vals = {k: v for k, v in gates[name].items() if k != "ok"}
        print(f"FAIL {name}: {json.dumps(vals, sort_keys=True)}",
              file=sys.stderr)
    if not failed:
        print("OK")
    return len(failed)


if __name__ == "__main__":
    sys.exit(main())
